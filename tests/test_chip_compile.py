"""The main serving path's Pallas kernels compile for a TPU v5e at
qwen2-vl-2b's published widths, with the blocks the planner picks.

Nothing runs: the TPU compiler builds each kernel for a described chip,
so what Mosaic would refuse on the device (gathers, unaligned slices,
shape casts, more VMEM than a kernel may claim) fails here, on the CPU.
The topology is described inside a module fixture, never at import —
only the test worker given this file loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.core import spx
from repro.core.pipeline import HARDWARE, TPU_V5E
from repro.core.quantized import QuantizedTensor
from repro.kernels import ops
from repro.models import lm as lm_mod
from repro.nn.layers import quantize_params
from repro.runtime import Runtime, planner

CFG = get_config("qwen2-vl-2b")
D, DH, HQ, HKV, FF = (CFG.d_model, CFG.dh, CFG.n_heads, CFG.n_kv_heads,
                      CFG.d_ff)
SLOTS, CHUNK, MAX_SEQ = 8, 32, 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert topo.devices[0].device_kind in HARDWARE
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep these out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# (name, K, N) of every quantized projection, and the M of a decode tick
# (8 slots x 1 token) and a prefill tick (8 slots x 32-token chunk)
PROJ = {"qkv_q": (D, HQ * DH), "qkv_kv": (D, HKV * DH),
        "o": (HQ * DH, D), "gate_up": (D, FF), "down": (FF, D)}


@pytest.mark.parametrize("scheme", ["sp2_4", "spx_8_x3"])
@pytest.mark.parametrize("m", [SLOTS, SLOTS * CHUNK])
@pytest.mark.parametrize("proj", sorted(PROJ))
def test_spx_matmul_compiles(one_chip, proj, m, scheme):
    k, n = PROJ[proj]
    bits = spx.code_width(spx.scheme_levels(scheme))
    packed = bits <= 4
    plan = planner.plan_matmul(m, k, n, weight_bits=bits, act_bytes=4,
                               packed=packed, hw=TPU_V5E)
    assert plan is not None, (proj, m, scheme)
    assert plan.vmem_bytes <= TPU_V5E.vmem_bytes, plan
    qt = QuantizedTensor(
        _sds((k, n // 2 if packed else n), jnp.uint8, one_chip),
        _sds((1, n), jnp.float32, one_chip), scheme, packed)
    x = _sds((m, k), jnp.float32, one_chip)
    with jax.default_matmul_precision(JAMBA.matmul_precision):
        compiled = _compile(
            lambda x, qt: ops.spx_matmul(x, qt, impl="pallas"), x, qt)
    assert "tpu_custom_call" in compiled.as_text()


def _paged_args(one_chip, w, quant):
    kv_scheme = "spx_8_x3" if quant else None
    page = planner.plan_kv_pages(HKV, DH, rep=HQ // HKV, act_bytes=4,
                                 kv_scheme=kv_scheme, hw=TPU_V5E)
    ps = page.page_size
    max_pages = MAX_SEQ // ps
    n_pages = SLOTS * max_pages
    plan = planner.plan_fused_decode(DH, rep=HQ // HKV, w=w, page_size=ps,
                                     act_bytes=4, kv_scheme=kv_scheme,
                                     hw=TPU_V5E)
    assert plan.vmem_bytes <= TPU_V5E.vmem_bytes, plan
    if quant:
        pool = {"codes": _sds((n_pages, HKV, ps, DH), jnp.uint8, one_chip),
                "scale": _sds((n_pages, HKV, spx.kv_scale_lanes(ps)),
                              jnp.float32, one_chip)}
    else:
        pool = _sds((n_pages, HKV, ps, DH), jnp.float32, one_chip)
    q = _sds((SLOTS, w, HQ, DH), jnp.float32, one_chip)
    bt = _sds((SLOTS, max_pages), jnp.int32, one_chip)
    lens = _sds((SLOTS,), jnp.int32, one_chip)
    return kv_scheme, (q, pool, pool, bt, lens, lens)


@pytest.mark.parametrize("w", [1, 5])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "spx_8_x3"])
def test_paged_decode_ragged_compiles(one_chip, w, quant):
    kv_scheme, (q, pool, _, bt, lens, _) = _paged_args(one_chip, w, quant)
    # one layer's pools, stacked as the serving scan carries them
    pools = jax.tree_util.tree_map(
        lambda a: _sds((1,) + a.shape, a.dtype, one_chip), pool)
    ops.reset_op_calls()
    compiled = _compile(
        lambda *a: ops.paged_decode_ragged(*a, layer=0, kv_scheme=kv_scheme,
                                           impl="pallas"),
        q, pools, pools, bt, lens, lens)
    assert "tpu_custom_call" in compiled.as_text()
    # SPx pages decode by the lane gather (dh = 128)
    assert ops.lut_lookups() == (
        {("paged_decode_ragged_quant", "gather"): 1} if quant else {})


@pytest.mark.parametrize("seq", [256, 1024])
def test_flash_attention_compiles(one_chip, seq):
    plan = planner.plan_attention(seq, seq, DH, act_bytes=4, hw=TPU_V5E)
    assert plan.vmem_bytes <= TPU_V5E.vmem_bytes, plan
    q = _sds((1, HQ, seq, DH), jnp.float32, one_chip)
    kv = _sds((1, HKV, seq, DH), jnp.float32, one_chip)
    compiled = _compile(
        lambda q, k, v: ops.flash_attention(q, k, v, impl="pallas"),
        q, kv, kv)
    assert "tpu_custom_call" in compiled.as_text()


def test_spx_matmul_compiles_on_a_four_chip_mesh(topo):
    """GSPMD cannot partition a Mosaic call: under a 2x2 mesh the kernel
    runs whole on every chip, after its column-sharded codes are
    gathered."""
    mesh = Mesh(np.array(topo.devices[:4]).reshape(1, 4), ("data", "model"))
    k, n = PROJ["gate_up"]
    cols = NamedSharding(mesh, P(None, "model"))
    qt = QuantizedTensor(_sds((k, n // 2), jnp.uint8, cols),
                         _sds((1, n), jnp.float32, cols), "sp2_4", True)
    x = _sds((SLOTS, k), jnp.float32, NamedSharding(mesh, P()))
    hlo = _compile(lambda x, qt: ops.spx_matmul(x, qt, impl="pallas",
                                                mesh=mesh), x, qt).as_text()
    assert "tpu_custom_call" in hlo and "all-gather" in hlo


# jamba2-mini (AI21-Jamba2-Mini's widths): its Mamba projections, x_proj's
# 288 columns (one int4 lane group of 144), and the grouped expert kernel
# at the tile rows a decode tick (64 rows x top-2 over 16 experts) and a
# prefill tick (64 x 32 rows) pick, at the entry's matmul precision (f32
# accuracy), as its serving steps trace them
JAMBA = get_config("jamba2-mini")
JAMBA_DI = JAMBA.ssm_expand * JAMBA.d_model
JAMBA_PROJ = {"in_proj": (JAMBA.d_model, 2 * JAMBA_DI),
              "x_proj": (JAMBA_DI, JAMBA.ssm_dt_rank + 2 * JAMBA.ssm_d_state),
              "dt_proj": (JAMBA.ssm_dt_rank, JAMBA_DI),
              "out_proj": (JAMBA_DI, JAMBA.d_model)}


@pytest.mark.parametrize("m", [64, 64 * CHUNK])
@pytest.mark.parametrize("proj", sorted(JAMBA_PROJ))
def test_jamba_projection_compiles(one_chip, proj, m):
    k, n = JAMBA_PROJ[proj]
    qt = QuantizedTensor(_sds((k, n // 2), jnp.uint8, one_chip),
                         _sds((1, n), jnp.float32, one_chip), "sp2_4", True)
    x = _sds((m, k), jnp.float32, one_chip)
    with jax.default_matmul_precision(JAMBA.matmul_precision):
        compiled = _compile(
            lambda x, qt: ops.spx_matmul(x, qt, impl="pallas"), x, qt)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [64, 64 * CHUNK])
@pytest.mark.parametrize("which", ["gate_up", "down"])
def test_spx_expert_matmul_compiles(one_chip, which, rows):
    from repro.nn.moe import expert_tile_rows
    e, d, f = JAMBA.n_experts, JAMBA.d_model, JAMBA.d_ff
    k, n = (d, f) if which == "gate_up" else (f, d)
    pairs = rows * JAMBA.top_k
    bm = expert_tile_rows(pairs, e)
    g = -(-pairs // bm) + e
    qt = QuantizedTensor(_sds((e, k, n // 2), jnp.uint8, one_chip),
                         _sds((e, 1, n), jnp.float32, one_chip), "sp2_4",
                         True)
    with jax.default_matmul_precision(JAMBA.matmul_precision):
        compiled = _compile(
            lambda x, qt, te, tr: ops.spx_expert_matmul(
                x, qt, te, tr, bm=bm, impl="pallas"),
            _sds((g * bm, k), jnp.float32, one_chip), qt,
            _sds((g,), jnp.int32, one_chip),
            _sds((g,), jnp.int32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


# The paged steps at qwen2-vl-2b's published widths and depth: chat's f32
# pool (32 slots) and longgen's SPx pool (16 slots), max_seq 2048 each.
# The layer scan carries the pools and the kernels write and read them in
# place, so no program copies, slices, updates or allocates a whole pool
# or one layer of it.

_POOL_OPS = re.compile(r"copy|dynamic-slice|dynamic-update-slice")


def _pool_sized_ops(hlo: str, shapes: set) -> list:
    """Instructions outside fused computations that copy, slice, update
    or allocate an array of one of ``shapes``. XLA's memory-space
    assignment may stage a small leaf in VMEM for the whole step with an
    async copy-start/copy-done pair; that moves the buffer whole, and is
    not counted."""
    fused = set(re.findall(r" fusion\(.*calls=%([\w.-]+)", hlo))
    found, comp = [], None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (\S+) ([a-z-]+)\(", line)
        if comp in fused or not m:
            continue
        name, result, op = m.groups()
        kind = {"fusion": name, "custom-call": line}.get(op, op)
        if op in ("copy-start", "copy-done") or not (
                _POOL_OPS.search(kind) or "AllocateBuffer" in kind):
            continue
        for dims in re.findall(r"[a-z0-9]+\[([0-9,]*)\]", result):
            if tuple(int(d) for d in dims.split(",") if d) in shapes:
                found.append(name)
    return found


@pytest.mark.parametrize("kv,slots", [("f32", 32), ("spx_8_x3", 16)])
def test_paged_steps_update_the_pools_in_place(one_chip, kv, slots):
    quant = kv != "f32"
    ps = planner.plan_kv_pages(HKV, DH, rep=HQ // HKV, act_bytes=4,
                               kv_scheme=kv if quant else None,
                               hw=TPU_V5E).page_size
    pps = MAX_SEQ // ps

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(a.shape, a.dtype, one_chip), tree)

    params = sds(jax.eval_shape(lambda: quantize_params(
        lm_mod.lm_init(jax.random.PRNGKey(0), CFG), "sp2_4")))
    caches = sds(jax.eval_shape(lambda: lm_mod.paged_init_caches(
        CFG, slots * pps, ps, dtype=jnp.float32, kv_quant=quant)))
    leaves = jax.tree_util.tree_leaves(caches)
    pool = sum(a.size * a.dtype.itemsize for a in leaves)
    shapes = {tuple(a.shape) for a in leaves} \
        | {tuple(a.shape[1:]) for a in leaves}
    rt = Runtime(impl="pallas", q_chunk=256, kv_quant=quant,
                 kv_scheme=kv if quant else "uniform8")
    i32 = lambda *s: _sds(s, jnp.int32, one_chip)
    for fn, width in ((lm_mod.lm_paged_step, CHUNK),
                      (lm_mod.lm_paged_fused_step, 1)):
        ops.reset_op_calls()
        compiled = jax.jit(fn, static_argnums=(7, 8),
                           donate_argnums=(6,)).lower(
            params, i32(slots, width), i32(slots), i32(slots, pps),
            i32(slots), i32(slots, 2), caches, CFG, rt).compile()
        # one write kernel in the scan body, run once a layer
        assert ops.op_impls()[("paged_kv_write", "pallas")] == 1
        hlo = compiled.as_text()
        assert "paged_kv_write_pallas" in hlo
        assert _pool_sized_ops(hlo, shapes) == [], fn.__name__
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == pool, fn.__name__
        if width == 1:
            assert mem.temp_size_in_bytes < 0.02 * pool, mem


# Tensor-parallel serving (ServeConfig.shards > 1) head-shards the pools
# over the mesh's model axis where it divides Hkv (qwen2-vl-2b: 2 KV
# heads), and replicates them where it does not. The write and the ragged
# decode kernels then run on each device's own heads: no pool, and no
# layer of one, is gathered, and the carried pools keep their sharding.
# Four of the model's layers keep the compile short.

@pytest.mark.parametrize("kv,shards", [("f32", 2), ("spx_8_x3", 2),
                                       ("f32", 4)])
def test_sharded_paged_steps_gather_no_pool(topo, kv, shards):
    import dataclasses
    from repro.sharding import ShardingPolicy
    cfg = dataclasses.replace(CFG, n_layers=4)
    quant = kv != "f32"
    slots = 32 if kv == "f32" else 16
    ps = planner.plan_kv_pages(HKV, DH, rep=HQ // HKV, act_bytes=4,
                               kv_scheme=kv if quant else None,
                               hw=TPU_V5E).page_size
    pps = MAX_SEQ // ps
    mesh = Mesh(np.array(topo.devices[:shards]).reshape(1, shards),
                ("data", "model"))
    policy = ShardingPolicy(cfg, mesh, fsdp=False, parallelism="tp")

    def sds(tree, specs):
        return jax.tree_util.tree_map(
            lambda a, s: _sds(a.shape, a.dtype, NamedSharding(mesh, s)),
            tree, specs)

    params = jax.eval_shape(lambda: quantize_params(
        lm_mod.lm_init(jax.random.PRNGKey(0), cfg), "sp2_4"))
    params = sds(params, policy.param_specs(params))
    caches = jax.eval_shape(lambda: lm_mod.paged_init_caches(
        cfg, slots * pps, ps, dtype=jnp.float32, kv_quant=quant))
    specs = policy.paged_state_specs(caches)
    caches = sds(caches, specs)
    sharded = HKV % shards == 0
    leaves = jax.tree_util.tree_leaves(caches)
    pool = sum(a.size * a.dtype.itemsize for a in leaves)
    shapes = {tuple(a.shape) for a in leaves} \
        | {tuple(a.shape[1:]) for a in leaves}
    rt = Runtime(impl="pallas", q_chunk=256, kv_quant=quant,
                 kv_scheme=kv if quant else "uniform8", mesh=mesh,
                 model_axis="model")
    rep = NamedSharding(mesh, P())
    i32 = lambda *s: _sds(s, jnp.int32, rep)
    for fn, width in ((lm_mod.lm_paged_step, CHUNK),
                      (lm_mod.lm_paged_fused_step, 1)):
        compiled = jax.jit(fn, static_argnums=(7, 8),
                           donate_argnums=(6,)).lower(
            params, i32(slots, width), i32(slots), i32(slots, pps),
            i32(slots), i32(slots, 2), caches, cfg, rt).compile()
        hlo = compiled.as_text()
        gathered = [line for line in hlo.splitlines()
                    if re.search(r"= \S+ all-gather(-start)?\(", line)
                    and any(tuple(int(d) for d in dims.split(",") if d)
                            in shapes for dims in re.findall(
                                r"[a-z0-9]+\[([0-9,]*)\]",
                                line.split("=")[1].split("all-gather")[0]))]
        assert gathered == [], (fn.__name__, gathered[:2])
        assert _pool_sized_ops(hlo, shapes) == [], fn.__name__
        # the carried pools come out sharded as they went in
        want = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda s: isinstance(s, P))
        got = jax.tree_util.tree_leaves(compiled.output_shardings[1])
        assert all(g.is_equivalent_to(NamedSharding(mesh, s), a.ndim)
                   for g, s, a in zip(got, want, leaves)), fn.__name__
        # every device holds, and updates in place, its share of the pool
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == pool // (shards if sharded
                                                   else 1), fn.__name__
