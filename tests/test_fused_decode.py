"""The ragged decode megakernel (ops.paged_decode_ragged) and the serving
engine's fused decode tick (docs/SERVING.md §megakernel):

* interpret-vs-ref kernel parity per KV scheme (dense, uniform8, sp2_8,
  spx_8_x3) across the whole ragged surface — q_len from 0 (inactive
  slot) through the full K+1 verify window, attend_len straddling page
  boundaries — plus exact zeros for padded window rows,
* the non-negotiable invariant: greedy engine outputs with the megakernel
  ON are bit-identical to the unfused per-call decode path, across
  {plain, kv_quant} x {spec on, spec off},
* ONE launch per decode tick: the fused step traces the ragged op exactly
  once, compiles exactly once, and never retraces across ticks with
  varying attend_len / n_valid (no pow2-window padding to bucket on),
* planner sizing (codes+scale pages + resident LUT) and the autotune key
  separating kv_scheme and the spec window,
* knobs: REPRO_FUSED_DECODE=0 opts out, explicit fused_decode=True on a
  dense engine is an error, the env default degrades silently there.

No hypothesis dependency — collected on the bare tier-1 environment.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import spx
from repro.kernels import ops
from repro.models import lm as lm_mod
from repro.nn.attention import quantize_pages
from repro.runtime import Runtime, planner
from repro.serving.engine import Request, ServeConfig, ServeEngine

jax.config.update("jax_platform_name", "cpu")

RT = Runtime(impl="ref", q_chunk=16)

SCHEMES = (None, "uniform8", "sp2_8", "spx_8_x3")


# ---------------------------------------------------------------------------
# Kernel parity: interpret (the Pallas body on CPU) vs the jnp oracle
# ---------------------------------------------------------------------------

def _pools(rng, b, hkv, ps, max_pages, dh, scheme):
    """Random page pools (+1 spare page so block tables can alias), the
    block tables, and the (k, v) pool pair in the layout ``scheme`` asks
    for (dense arrays, or codes+scale dicts)."""
    n_pages = 1 + b * max_pages
    kp = jnp.asarray(rng.standard_normal((n_pages, hkv, ps, dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, hkv, ps, dh)),
                     jnp.float32)
    bt = jnp.asarray(rng.integers(0, n_pages, (b, max_pages)), jnp.int32)
    if scheme is None:
        return kp, vp, bt
    return quantize_pages(kp, scheme), quantize_pages(vp, scheme), bt


def _stacked(kp, vp):
    """The (k, v) pools as the ragged op takes them: every layer's pools
    on a leading axis, here one layer (read at ``layer=0``)."""
    return jax.tree_util.tree_map(lambda a: a[None], (kp, vp))


# dh=128 tiles take the lane-gather lookup for 8-bit codes, dh=32 the
# select chain (kernels/decode.py)
@pytest.mark.parametrize("dh", [32, 128])
@pytest.mark.parametrize("scheme", SCHEMES)
def test_ragged_interpret_matches_ref(scheme, dh):
    rng = np.random.default_rng(11)
    b, hq, hkv, ps, mp, w = 4, 4, 2, 8, 4, 4   # verify window K+1=4
    kp, vp, bt = _pools(rng, b, hkv, ps, mp, dh, scheme)
    q = jnp.asarray(rng.standard_normal((b, w, hq, dh)), jnp.float32)
    # ragged surface: q_len 0 (inactive) .. w (full window); ctx at and
    # around a page boundary so the per-slot trip count changes mid-batch
    ctx = jnp.asarray([0, ps - 1, ps, ps + 1], jnp.int32)
    qlen = jnp.asarray([0, 1, 3, w], jnp.int32)
    kw = dict(kv_scheme=scheme) if scheme else {}
    want = ops.paged_decode_ragged(q, *_stacked(kp, vp), bt, ctx, qlen,
                                   layer=0, impl="ref", **kw)
    got = ops.paged_decode_ragged(q, *_stacked(kp, vp), bt, ctx, qlen,
                                  layer=0, impl="interpret", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    # rows past q_len (and the whole inactive slot 0) are EXACT zeros in
    # both impls — the engine relies on never reading garbage there
    for out in (np.asarray(want), np.asarray(got)):
        assert (out[0] == 0).all()
        assert (out[1, 1:] == 0).all()
        assert (out[2, 3:] == 0).all()
        assert (out[3] != 0).any()


def test_ragged_w1_bit_identical_to_paged_attention():
    """W == 1 is plain decode: the ragged ref must equal the existing
    single-token paged-attention ref bit for bit (attend_len = ctx + 1),
    which is what makes fused-vs-unfused greedy outputs identical."""
    rng = np.random.default_rng(5)
    b, hq, hkv, dh, ps, mp = 3, 4, 2, 32, 8, 3
    kp, vp, bt = _pools(rng, b, hkv, ps, mp, dh, None)
    q = jnp.asarray(rng.standard_normal((b, 1, hq, dh)), jnp.float32)
    ctx = jnp.asarray([ps - 1, ps, 2 * ps + 3], jnp.int32)
    ones = jnp.ones((b,), jnp.int32)
    ragged = ops.paged_decode_ragged(q, *_stacked(kp, vp), bt, ctx, ones,
                                     layer=0, impl="ref")
    plain = ops.paged_attention(q[:, 0], kp, vp, bt, ctx + 1, impl="ref")
    assert (np.asarray(ragged[:, 0]) == np.asarray(plain)).all()


def test_ragged_quant_needs_scheme():
    rng = np.random.default_rng(0)
    kq, vq, bt = _pools(rng, 2, 1, 8, 2, 16, "uniform8")
    q = jnp.zeros((2, 1, 2, 16), jnp.float32)
    lens = jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match="kv_scheme"):
        ops.paged_decode_ragged(q, *_stacked(kq, vq), bt, lens, lens,
                                layer=0, impl="ref")


def test_registry_has_ragged_ops():
    from repro.runtime import registry
    for op in ("paged_decode_ragged", "paged_decode_ragged_quant"):
        assert set(registry.available_impls(op)) >= {"ref", "interpret"}
        assert registry.resolve(op, "auto").impl == "ref"   # CPU


# ---------------------------------------------------------------------------
# One launch per tick + the pow2-padding retrace hazard
# ---------------------------------------------------------------------------

def _tiny_cfg():
    # pinned exact-greedy workload (see tests/test_spec_decode.py): vocab
    # 32 keeps random-init top-2 logit gaps wide, so equality assertions
    # compare decode paths instead of coin-flip near-ties
    return dataclasses.replace(reduced(get_config("gemma-2b"), vocab=32),
                               head_dim=128)


def test_fused_step_single_trace_across_ragged_ticks():
    """The megakernel step compiles ONCE and traces the ragged attention
    op ONCE — varying attend_len / n_valid across ticks rides in the
    scalar-prefetch data, not the trace, so there is no pow2 bucketing
    and no retrace (the Runtime-test discipline, now for raggedness)."""
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    caches = lm_mod.paged_init_caches(cfg, n_pages=8, page_size=8,
                                      dtype=jnp.float32)
    step = jax.jit(lm_mod.lm_paged_fused_step, static_argnums=(7, 8))
    bt = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    w = 4
    tokens = jnp.zeros((2, w), jnp.int32)
    sidx = jnp.zeros((2, 2), jnp.int32)               # attn-only sentinels
    ops.reset_op_calls()
    ticks = [([3, 9], [1, 4]), ([4, 13], [4, 1]),     # ragged + page
             ([8, 14], [2, 3]), ([0, 17], [0, 2])]    # boundary crossings
    for ctx, nv in ticks:
        logits, caches = step(params, tokens, jnp.asarray(ctx, jnp.int32),
                              bt, jnp.asarray(nv, jnp.int32), sidx, caches,
                              cfg, RT)
    assert logits.shape == (2, w, cfg.vocab_size)
    assert step._cache_size() == 1                    # zero retrace
    calls = ops.op_calls()
    # one trace, one ragged-op call site inside it (the layer scan traces
    # its body once) — and the legacy per-call decode ops never appear
    assert calls.get("paged_decode_ragged") == 1
    assert calls.get("paged_attention") is None
    assert calls.get("paged_attention_quant") is None


def _pallas_kernels(jaxpr) -> list[str]:
    """Source names of the Pallas kernels a jaxpr launches, nested
    jaxprs (the layer scan's body) included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            src = eqn.params["jaxpr"].debug_info.func_src_info
            out.append(src.split()[0])
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _pallas_kernels(sub)
    return out


def test_engine_fused_tick_is_one_compile_one_launch():
    """Whatever earlier tests traced in this process: the engine's fused
    step holds one ragged kernel launch, the engine launches it once per
    decode tick, and ticks after a warm one neither trace nor compile
    it again, though attend_len and n_valid vary from tick to tick."""
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    eng = ServeEngine(params, cfg,
                      ServeConfig(batch_slots=2, max_seq=64, quantize=None,
                                  kv_layout="paged", fused_decode=True),
                      rt=RT)
    # the step's program, traced with the Pallas kernels in place of the
    # jnp oracles: the in-place KV write and the ragged decode, and no
    # legacy per-call paged attention (the layer scan traces its body
    # once)
    b = eng.batch_slots
    traced = jax.jit(lm_mod.lm_paged_fused_step, static_argnums=(7, 8)).trace(
        eng.params, jnp.zeros((b, 1), jnp.int32), jnp.zeros(b, jnp.int32),
        jnp.asarray(eng.block_tables), jnp.ones(b, jnp.int32),
        jnp.asarray(eng._state_idx), eng.caches, cfg,
        dataclasses.replace(RT, impl="interpret"))
    assert _pallas_kernels(traced.jaxpr.jaxpr) == ["_kv_write_kernel",
                                                   "_ragged_kernel"]

    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, int(rng.integers(4, 24))).astype(np.int32),
        max_new_tokens=6) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.step()                                  # warm: prefill + decode
    assert eng.metrics()["launches"] == {"prefill": 1, "decode": 1}
    events = []

    def on_event(event, duration, **kw):
        if "lm_paged_fused_step" in str(kw.get("fun_name", "")):
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    decode_ticks = 1
    try:
        # ragged prompt lengths + continuous batching vary attend_len and
        # n_valid across ticks; a tick decodes where it emits more tokens
        # than first tokens, and then launches the fused step once
        while eng.has_work():
            before = [len(r.output) for r in reqs]
            launched = eng.metrics()["launches"]["decode"]
            eng.step()
            firsts = sum(1 for r, n in zip(reqs, before)
                         if n == 0 and r.output)
            decoded = sum(len(r.output) for r in reqs) - sum(before) \
                - firsts
            launched = eng.metrics()["launches"]["decode"] - launched
            assert launched == (1 if decoded else 0)
            decode_ticks += launched
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    # ... and nothing traced or compiled the fused step after the warm
    # tick
    assert events == []
    m = eng.metrics()
    assert m["fused_decode"] is True
    assert m["launches"]["decode"] == decode_ticks > 2
    assert m["model_calls"] == sum(m["launches"].values())


# ---------------------------------------------------------------------------
# Engine bit-identity: megakernel on vs off
# ---------------------------------------------------------------------------

def _drive(params, cfg, prompts, *, fused, kv_quant=False, spec=False,
           new_tokens=8):
    rt = dataclasses.replace(RT, kv_quant=kv_quant,
                             kv_scheme="spx_8_x3" if kv_quant else RT.kv_scheme)
    eng = ServeEngine(params, cfg,
                      ServeConfig(batch_slots=2, max_seq=64, quantize="sp2_4",
                                  kv_layout="paged", fused_decode=fused,
                                  spec_decode=True if spec else None,
                                  spec_k=3 if spec else None),
                      rt=rt)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
    out = {r.rid: list(r.output) for r in eng.run()}
    return out, eng.metrics()


@pytest.mark.parametrize("kv_quant", [False, True])
@pytest.mark.parametrize("spec", [False, True])
def test_fused_greedy_bit_identical_to_unfused(kv_quant, spec):
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    # repetition-heavy tails give the n-gram drafter something to accept
    prompts = [np.tile(rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
                       3) for _ in range(4)]
    fused_out, fm = _drive(params, cfg, prompts, fused=True,
                           kv_quant=kv_quant, spec=spec)
    plain_out, pm = _drive(params, cfg, prompts, fused=False,
                           kv_quant=kv_quant, spec=spec)
    assert fused_out == plain_out
    assert fm["fused_decode"] and not pm["fused_decode"]
    assert fm["tokens_generated"] == pm["tokens_generated"]
    if spec:
        # speculation stays effective through the megakernel: fewer model
        # calls than tokens means some windows accepted drafts
        assert fm["draft_acceptance_rate"] > 0.0


def test_fused_sampled_matches_unfused_key_chain():
    """Temperature sampling: the fused tick consumes the per-request key
    chain exactly like the unfused one (one draw per emitted token), so
    seeded sampled outputs are identical too."""
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(2)]

    def run(fused):
        eng = ServeEngine(params, cfg,
                          ServeConfig(batch_slots=2, max_seq=64, quantize=None,
                                      kv_layout="paged", fused_decode=fused),
                          rt=RT)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=6,
                               temperature=0.8, seed=17 + i))
        return {r.rid: list(r.output) for r in eng.run()}

    assert run(True) == run(False)


# ---------------------------------------------------------------------------
# Knobs
# ---------------------------------------------------------------------------

def test_fused_decode_knobs(monkeypatch):
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    # default ON for paged engines
    assert ServeEngine(
        params, cfg, ServeConfig(quantize=None, kv_layout="paged"),
        rt=RT).fused_decode is True
    # REPRO_FUSED_DECODE=0 flips the default off
    monkeypatch.setenv("REPRO_FUSED_DECODE", "0")
    assert ServeEngine(
        params, cfg, ServeConfig(quantize=None, kv_layout="paged"),
        rt=RT).fused_decode is False
    monkeypatch.delenv("REPRO_FUSED_DECODE")
    # dense engine: the env/default degrades silently ...
    dense = ServeEngine(params, cfg,
                        ServeConfig(quantize=None, kv_layout="dense"), rt=RT)
    assert dense.fused_decode is False
    # ... but an explicit True there is a caller error
    with pytest.raises(ValueError, match="fused_decode"):
        ServeEngine(params, cfg,
                    ServeConfig(quantize=None, kv_layout="dense",
                                fused_decode=True),
                    rt=RT)


# ---------------------------------------------------------------------------
# Planner model + autotune key
# ---------------------------------------------------------------------------

def test_plan_fused_decode_byte_model():
    dense = planner.plan_fused_decode(128, rep=4, w=5, page_size=16,
                                      act_bytes=4)
    quant = planner.plan_fused_decode(128, rep=4, w=5, page_size=16,
                                      act_bytes=4, kv_scheme="spx_8_x3")
    assert dense.rows == quant.rows == 20
    assert dense.lut_bytes == 0
    # 8-bit code schemes: 256-entry f32 LUT resident for the launch
    assert quant.lut_bytes == 4 * 256
    # codes+scale pages stream fewer bytes than f32 pages, so the quant
    # kernel's margin is strictly better at the same window
    assert quant.margin > dense.margin
    assert dense.vmem_bytes > 0 and quant.vmem_bytes > 0
    # a wider window adds compute per streamed page, never load
    w1 = planner.plan_fused_decode(128, rep=4, w=1, page_size=16,
                                   act_bytes=4)
    assert dense.margin > w1.margin


def test_fused_decode_key_separates_scheme_and_window():
    base = dict(b=4, hkv=2, rep=4, dh=128, page_size=16, max_pages=8)
    k_dense = planner.fused_decode_key(w=1, kv_scheme=None, **base)
    k_quant = planner.fused_decode_key(w=1, kv_scheme="spx_8_x3", **base)
    k_verify = planner.fused_decode_key(w=5, kv_scheme=None, **base)
    k_uniform = planner.fused_decode_key(w=1, kv_scheme="uniform8", **base)
    assert len({k_dense, k_quant, k_verify, k_uniform}) == 4
    # and the measured-plan table keys on it: a winner cached for one
    # scheme/window is invisible to the others
    planner.clear_plan_cache()
    plan = planner.plan_fused_decode(128, rep=4, w=1, page_size=16)
    assert planner.measured_best(k_dense, [plan], lambda p: 1.0) is plan
    assert planner.measured_plan(k_dense) is plan
    assert planner.measured_plan(k_quant) is None
    assert planner.measured_plan(k_verify) is None
    planner.clear_plan_cache()
