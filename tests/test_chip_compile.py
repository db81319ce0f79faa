"""The main serving path's Pallas kernels compile for a TPU v5e at
qwen2-vl-2b's published widths, with the blocks the planner picks.

Nothing runs: the TPU compiler builds each kernel for a described chip,
so what Mosaic would refuse on the device (gathers, unaligned slices,
shape casts, more VMEM than a kernel may claim) fails here, on the CPU.
The topology is described inside a module fixture, never at import —
only the test worker given this file loads the TPU library.
"""
import os

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
from repro.runtime import planner

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
                "scale": _sds((n_pages, HKV, ps, 1), jnp.float32, one_chip)}
    else:
        pool = _sds((n_pages, HKV, ps, DH), jnp.float32, one_chip)
    q = _sds((SLOTS, w, HQ, DH), jnp.float32, one_chip)
    bt = _sds((SLOTS, max_pages), jnp.int32, one_chip)
    lens = _sds((SLOTS,), jnp.int32, one_chip)
    return kv_scheme, (q, pool, pool, bt, lens, lens)


@pytest.mark.parametrize("w", [1, 5])
@pytest.mark.parametrize("quant", [False, True], ids=["f32", "spx_8_x3"])
def test_paged_decode_ragged_compiles(one_chip, w, quant):
    kv_scheme, args = _paged_args(one_chip, w, quant)
    ops.reset_op_calls()
    compiled = _compile(
        lambda *a: ops.paged_decode_ragged(*a, kv_scheme=kv_scheme,
                                           impl="pallas"), *args)
    assert "tpu_custom_call" in compiled.as_text()
    # SPx pages decode by the lane gather (dh = 128)
    assert ops.lut_lookups() == (
        {("paged_decode_ragged_quant", "gather"): 1} if quant else {})


def test_paged_attention_quant_compiles(one_chip):
    """The unragged quantized decode kernel, at the ragged one's pages."""
    kv_scheme, (_, pool, _, bt, lens, _) = _paged_args(one_chip, 1, True)
    q = _sds((SLOTS, HQ, DH), jnp.float32, one_chip)
    ops.reset_op_calls()
    compiled = _compile(
        lambda q, kp, vp, bt, ctx: ops.paged_attention_quant(
            q, kp, vp, bt, ctx, kv_scheme=kv_scheme, impl="pallas"),
        q, pool, pool, bt, lens)
    assert "tpu_custom_call" in compiled.as_text()
    assert ops.lut_lookups() == {("paged_attention_quant", "gather"): 1}


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
