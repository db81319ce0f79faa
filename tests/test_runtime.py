"""The execution-plan runtime: registry dispatch, block planning, and the
frozen Runtime as a static jit argument (retrace regression)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_configs
from repro.core.pipeline import TPU_V5E
from repro.core.quantized import quantize_weight
from repro.kernels import ops
from repro.runtime import (KernelUnavailable, Runtime, planner, registry)

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

def test_registry_ops_registered():
    assert set(registry.registered_ops()) >= {"spx_matmul", "flash_attention"}
    for op in ("spx_matmul", "flash_attention"):
        assert set(registry.available_impls(op)) >= {"ref", "interpret"}


def test_registry_auto_resolves_ref_on_cpu():
    for op in ("spx_matmul", "flash_attention"):
        assert registry.resolve(op, "auto").impl == "ref"


def test_registry_explicit_and_unknown():
    assert registry.resolve("spx_matmul", "interpret").impl == "interpret"
    with pytest.raises(KernelUnavailable):
        registry.resolve("spx_matmul", "cuda")
    with pytest.raises(KernelUnavailable):
        registry.resolve("not_an_op", "ref")


def test_registry_resolution_is_cached():
    a = registry.resolve("spx_matmul", "auto")
    b = registry.resolve("spx_matmul", "auto")
    assert a is b


# ---------------------------------------------------------------------------
# Planner: budget + divisibility across the bundled model configs
# ---------------------------------------------------------------------------

def _config_matmul_shapes(cfg):
    """The hot (K, N) weight shapes of one architecture."""
    d, dh = cfg.d_model, cfg.dh
    shapes = [(d, cfg.n_heads * dh), (d, cfg.n_kv_heads * dh),
              (cfg.n_heads * dh, d)]
    if cfg.d_ff:
        shapes += [(d, cfg.d_ff), (cfg.d_ff, d)]
    return shapes


@pytest.mark.parametrize("arch", list_configs())
@pytest.mark.parametrize("bits", [4, 8])
def test_plans_respect_budget_and_divisibility(arch, bits):
    cfg = get_config(arch)
    for m in (8, 256, 4096):
        for k_dim, n_dim in _config_matmul_shapes(cfg):
            plan = planner.plan_matmul(m, k_dim, n_dim, weight_bits=bits,
                                       packed=(bits == 4))
            if plan is None:      # ragged: legal, falls back to ref
                continue
            assert n_dim % plan.bn == 0, (arch, k_dim, n_dim, plan)
            assert k_dim % plan.bk == 0, (arch, k_dim, n_dim, plan)
            if bits == 4:
                assert plan.bn % 2 == 0     # packed int4: even bn
            assert plan.vmem_bytes <= (TPU_V5E.vmem_bytes
                                       * planner.VMEM_BUDGET_FRACTION)


@pytest.mark.parametrize("arch", list_configs())
def test_attention_plans_divisible(arch):
    cfg = get_config(arch)
    if cfg.n_heads == 0:
        pytest.skip("no attention")
    for s in (128, 4096, 32768):
        plan = planner.plan_attention(s, s, cfg.dh)
        assert plan is not None
        assert s % plan.bq == 0 and s % plan.bkv == 0
        assert plan.vmem_bytes <= (TPU_V5E.vmem_bytes
                                   * planner.VMEM_BUDGET_FRACTION)


def test_plan_cache_hits():
    planner.plan_matmul(64, 256, 256, weight_bits=8)
    before = planner._plan_matmul_cached.cache_info().hits
    planner.plan_matmul(64, 256, 256, weight_bits=8)
    assert planner._plan_matmul_cached.cache_info().hits == before + 1


def test_hardware_table_by_device_kind():
    """One table keyed by device_kind: an unknown chip is an error, and
    off the chip the planners target v5e explicitly."""
    from repro.core.pipeline import current_hw, hw_for_kind
    assert hw_for_kind("TPU v5 lite") is TPU_V5E
    assert TPU_V5E.source
    with pytest.raises(LookupError, match="no hardware entry"):
        hw_for_kind("TPU v99")
    assert current_hw() is TPU_V5E
    assert (planner.plan_matmul(256, 1536, 8960, weight_bits=4,
                                packed=True)
            == planner.plan_matmul(256, 1536, 8960, weight_bits=4,
                                   packed=True, hw=TPU_V5E))


def test_ragged_dims_return_none():
    assert planner.plan_matmul(8, 250, 130, weight_bits=4) is None
    assert planner.plan_attention(7, 13, 64) is None


def test_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_BLOCKS_MATMUL", "64,128,128")
    plan = planner.plan_matmul(256, 256, 256, weight_bits=4)
    assert (plan.bm, plan.bn, plan.bk) == (64, 128, 128)
    monkeypatch.setenv("REPRO_BLOCKS_ATTN", "32,64")
    ap = planner.plan_attention(128, 128, 64)
    assert (ap.bq, ap.bkv) == (32, 64)
    # non-dividing pin -> ref fallback, not a crash
    monkeypatch.setenv("REPRO_BLOCKS_MATMUL", "64,100,100")
    assert planner.plan_matmul(256, 256, 256, weight_bits=4) is None


def test_measured_best_caches_winner():
    planner.clear_plan_cache()
    key = ("spx_matmul", 16, 256, 128, 4, True)
    plans = [planner.MatmulBlocks(128, 128, 128, False, 0.0, 0),
             planner.MatmulBlocks(64, 128, 128, False, 0.0, 0)]
    times = {id(plans[0]): 2.0, id(plans[1]): 1.0}
    best = planner.measured_best(key, plans, lambda p: times[id(p)])
    assert best is plans[1]
    # the winner is visible to later (including trace-time) lookups ...
    assert planner.measured_plan(key) is plans[1]
    # ... and the runner is not re-invoked for a known key
    assert planner.measured_best(key, plans, lambda p: 1 / 0) is plans[1]
    planner.clear_plan_cache()
    assert planner.measured_plan(key) is None


# ---------------------------------------------------------------------------
# Planned dispatch end to end (interpret impl runs the kernel body on CPU)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m,k,n", [(8, 128, 128), (48, 256, 128),
                                   (200, 384, 256)])
def test_planned_spx_matmul_matches_ref(m, k, n):
    rng = np.random.default_rng(m + k + n)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.05, jnp.float32)
    qt = quantize_weight(w, "sp2_4")
    want = ops.spx_matmul(x, qt, impl="ref")
    got = ops.spx_matmul(x, qt, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_planned_flash_attention_matches_ref():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 4, 64, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 2, 64, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 2, 64, 32)), jnp.float32)
    want = ops.flash_attention(q, k, v, causal=True, impl="ref")
    got = ops.flash_attention(q, k, v, causal=True, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# Frozen Runtime: hashability + retrace regression
# ---------------------------------------------------------------------------

def test_runtime_frozen_and_hashable():
    rt = Runtime(impl="ref", q_chunk=16)
    with pytest.raises(Exception):
        rt.impl = "pallas"
    assert rt == rt.replace()
    assert hash(rt) == hash(rt.replace())
    assert rt.replace(q_chunk=32) != rt
    assert isinstance(Runtime(data_axes=["data", "pod"]).data_axes, tuple)


def test_no_retrace_on_equal_runtime():
    """Replacing a Runtime with an equal-valued copy must hit the jit cache
    (zero recompiles) when it rides as a static argument."""
    rt = Runtime(impl="ref", q_chunk=8)
    traces = []

    def f_impl(x, rt):
        traces.append(1)
        return x * rt.q_chunk

    f = jax.jit(f_impl, static_argnums=1)
    x = jnp.ones((4,))
    f(x, rt)
    assert f._cache_size() == 1
    f(x, rt.replace())                      # equal values -> cache hit
    f(x, Runtime(impl="ref", q_chunk=8))    # fresh equal object -> cache hit
    assert f._cache_size() == 1
    assert len(traces) == 1
    f(x, rt.replace(q_chunk=16))            # different value -> one retrace
    assert f._cache_size() == 2


def test_engine_decode_reuses_compilation():
    """End-to-end: the serving engine's static (cfg, rt) jit arguments do
    not retrace across equal-valued Runtime replacements."""
    from repro.configs import reduced
    from repro.models import lm as lm_mod

    cfg = reduced(get_config("gemma-2b"), d_model=64, vocab=128)
    rt = Runtime(impl="ref", q_chunk=16)
    params = lm_mod.lm_init(jax.random.PRNGKey(0), cfg)
    caches = lm_mod.init_caches(cfg, 1, 16, dtype=jnp.float32)
    step = jax.jit(lm_mod.lm_decode_step, static_argnums=(4, 5))
    tok = jnp.zeros((1,), jnp.int32)
    pos = jnp.zeros((), jnp.int32)
    _, caches = step(params, tok, pos, caches, cfg, rt)
    n = step._cache_size()
    _, caches = step(params, tok, pos + 1, caches, cfg, rt.replace())
    assert step._cache_size() == n


def test_compile_cache_location(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set; otherwise
    the cache sits at the fixed <checkout>/.jax_cache."""
    import pathlib
    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = compile_cache.enable_compile_cache()
        root = pathlib.Path(__file__).resolve().parents[1]
        assert got == str(root / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
