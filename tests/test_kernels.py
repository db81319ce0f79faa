"""Per-kernel validation: pallas (interpret mode) vs pure-jnp ref oracle,
swept over shapes, dtypes, schemes and block sizes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl

from repro.core import spx
from repro.core.quantized import quantize_weight
from repro.kernels import ops, ref
from repro.kernels.decode import lut_decode, lut_operand
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.spx_matmul import spx_matmul_pallas

jax.config.update("jax_platform_name", "cpu")


def _mk(shape, dtype, seed, scale=1.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


# ---------------------------------------------------------------------------
# spx_matmul
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("width", [32, 256])
@pytest.mark.parametrize("scheme", ["sp2_4", "uniform8", "sp2_8",
                                    "spx_8_x3"])
def test_lut_decode_every_code_bit_identical(scheme, width):
    """Every code of the codebook, at shuffled lanes, decodes in a Pallas
    body to exactly ``lut[code]``: 8-bit tables on 128-lane-multiple
    tiles take the lane gather, the rest the select chain."""
    lut = spx.codebook(spx.scheme_levels(scheme))
    n = lut.shape[0]
    codes = np.random.default_rng(n + width).permutation(
        np.resize(np.arange(n, dtype=np.int32), 8 * width)).reshape(8, width)
    assert set(codes.ravel()) == set(range(n))
    ops.reset_op_calls()
    table, spec = lut_operand(lut, width, "lookup")

    def kernel(codes_ref, lut_ref, o_ref):
        o_ref[...] = lut_decode(codes_ref[...], lut_ref)

    tile = pl.BlockSpec((8, width), lambda: (0, 0))
    got = pl.pallas_call(
        kernel, in_specs=[tile, spec], out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((8, width), jnp.float32),
        interpret=True)(jnp.asarray(codes), table)
    want = np.asarray(lut)[codes]
    assert np.asarray(got).tobytes() == want.tobytes()
    path = "select" if scheme == "sp2_4" or width == 32 else "gather"
    assert ops.lut_lookups() == {("lookup", path): 1}


@pytest.mark.parametrize("m,n,k", [(8, 128, 128), (128, 128, 256),
                                   (64, 256, 128), (256, 128, 384)])
@pytest.mark.parametrize("scheme", ["sp2_4", "sp2_8", "spx_8_x3"])
def test_spx_matmul_shapes_schemes(m, n, k, scheme):
    x = _mk((m, k), jnp.float32, seed=m + n + k)
    w = _mk((k, n), jnp.float32, seed=1, scale=0.05)
    qt = quantize_weight(w, scheme)
    scale = qt.scale.reshape(1, n)
    want = ref.spx_matmul_ref(x, qt.codes, scale, qt.lut, packed=qt.packed)
    # a packed tile holds whole int4 lane groups (spx.int4_group)
    bn = 2 * spx.int4_group(n) if qt.packed else 128
    got = spx_matmul_pallas(x, qt.codes, scale, qt.lut, packed=qt.packed,
                            bm=min(128, m), bn=bn, bk=128, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_spx_matmul_dtypes(dtype):
    x = _mk((64, 256), dtype, seed=7)
    w = _mk((256, 128), jnp.float32, seed=8, scale=0.05)
    qt = quantize_weight(w, "sp2_4")
    scale = qt.scale.reshape(1, 128)
    want = ref.spx_matmul_ref(x, qt.codes, scale, qt.lut, packed=qt.packed)
    got = spx_matmul_pallas(x, qt.codes, scale, qt.lut, packed=qt.packed,
                            bm=64, bn=128, bk=128, interpret=True)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("blocks", [(32, 128, 128), (64, 64, 64),
                                    (128, 128, 384)])
def test_spx_matmul_block_sweep(blocks):
    bm, bn, bk = blocks
    x = _mk((128, 384), jnp.float32, seed=11)
    w = _mk((384, 256), jnp.float32, seed=12, scale=0.05)
    qt = quantize_weight(w, "sp2_8")   # unpacked path
    scale = qt.scale.reshape(1, 256)
    want = ref.spx_matmul_ref(x, qt.codes, scale, qt.lut, packed=qt.packed)
    got = spx_matmul_pallas(x, qt.codes, scale, qt.lut, packed=qt.packed,
                            bm=bm, bn=bn, bk=bk, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_ops_wrapper_pads_ragged_m_and_reshapes():
    x = _mk((3, 5, 256), jnp.float32, seed=13)   # leading dims + ragged M=15
    w = _mk((256, 128), jnp.float32, seed=14, scale=0.05)
    qt = quantize_weight(w, "sp2_4")
    want = ops.spx_matmul(x, qt, impl="ref")
    got = ops.spx_matmul(x, qt, impl="interpret")
    assert got.shape == (3, 5, 128)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_ops_wrapper_ragged_k_falls_back_to_ref():
    x = _mk((4, 100), jnp.float32, seed=15)      # K=100 has no aligned block
    w = _mk((100, 30), jnp.float32, seed=16)     # N=30 ragged too
    qt = quantize_weight(w, "sp2_4")
    ops.reset_op_calls()
    got = ops.spx_matmul(x, qt, impl="interpret")
    # the fallback is counted under the impl that actually ran
    assert ops.op_impls() == {("spx_matmul", "ref"): 1}
    ops.spx_matmul(_mk((4, 256), jnp.float32, seed=18),
                   quantize_weight(_mk((256, 256), jnp.float32, seed=19),
                                   "sp2_4"), impl="interpret")
    assert ops.op_impls()[("spx_matmul", "interpret")] == 1
    want = ops.spx_matmul(x, qt, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5)


def test_ops_wrapper_ragged_dims_raise_on_pallas():
    """On the chip a shape with no legal tiling is an error, not a silent
    run of the oracle."""
    x = _mk((4, 100), jnp.float32, seed=15)
    qt = quantize_weight(_mk((100, 30), jnp.float32, seed=16), "sp2_4")
    with pytest.raises(ValueError, match="no legal Pallas tiling"):
        ops.spx_matmul(x, qt, impl="pallas")
    q = _mk((1, 2, 7, 64), jnp.float32, seed=17)
    with pytest.raises(ValueError, match="no legal Pallas tiling"):
        ops.flash_attention(q, q, q, impl="pallas")


def test_quantized_matmul_end_to_end_snr():
    """The kernel path preserves the quantization SNR of the scheme."""
    x = _mk((32, 512), jnp.float32, seed=17)
    w = _mk((512, 256), jnp.float32, seed=18, scale=0.02)
    qt = quantize_weight(w, "sp2_8")
    exact = x @ w
    got = ops.spx_matmul(x, qt, impl="interpret", out_dtype=jnp.float32)
    snr = 20 * np.log10(np.linalg.norm(exact) /
                        np.linalg.norm(np.asarray(got) - np.asarray(exact)))
    assert snr > 25.0


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,skv,dh", [(128, 128, 64), (256, 256, 128),
                                       (128, 384, 64)])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_vs_ref(sq, skv, dh, causal):
    if causal and sq != skv:
        pytest.skip("causal requires square for this oracle comparison")
    bh = 3
    q = _mk((bh, sq, dh), jnp.float32, seed=21)
    k = _mk((bh, skv, dh), jnp.float32, seed=22)
    v = _mk((bh, skv, dh), jnp.float32, seed=23)
    want = ref.attention_ref(q, k, v, causal=causal)
    got = flash_attention_pallas(q, k, v, causal=causal, bq=64, bkv=128,
                                 interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = _mk((2, 128, 64), dtype, seed=31)
    k = _mk((2, 128, 64), dtype, seed=32)
    v = _mk((2, 128, 64), dtype, seed=33)
    want = ref.attention_ref(q, k, v, causal=True)
    got = flash_attention_pallas(q, k, v, causal=True, bq=64, bkv=64,
                                 interpret=True)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=3e-2, atol=3e-2)


def test_flash_block_sweep():
    q = _mk((2, 256, 64), jnp.float32, seed=41)
    k = _mk((2, 256, 64), jnp.float32, seed=42)
    v = _mk((2, 256, 64), jnp.float32, seed=43)
    want = ref.attention_ref(q, k, v, causal=True)
    for bq, bkv in [(32, 32), (64, 128), (256, 64), (128, 256)]:
        got = flash_attention_pallas(q, k, v, causal=True, bq=bq, bkv=bkv,
                                     interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-3, atol=2e-3, err_msg=f"{bq},{bkv}")


def test_gqa_wrapper_expansion():
    """ops.flash_attention handles GQA (Hq=8, Hkv=2) and matches per-group ref."""
    b, hq, hkv, s, dh = 2, 8, 2, 128, 64
    q = _mk((b, hq, s, dh), jnp.float32, seed=51)
    k = _mk((b, hkv, s, dh), jnp.float32, seed=52)
    v = _mk((b, hkv, s, dh), jnp.float32, seed=53)
    got = ops.flash_attention(q, k, v, causal=True, impl="interpret")
    kr = jnp.repeat(k, hq // hkv, axis=1).reshape(b * hq, s, dh)
    vr = jnp.repeat(v, hq // hkv, axis=1).reshape(b * hq, s, dh)
    want = ref.attention_ref(q.reshape(b * hq, s, dh), kr, vr,
                             causal=True).reshape(q.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# paged decode (the ragged decode kernel at W = 1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("ps", [8, 16])
def test_paged_attention_vs_ref(hq, hkv, ps):
    """Single-token decode through the ragged decode kernel (W = 1) in
    interpret mode (scalar-prefetched block table, online softmax over
    pages) vs the gather-everything ref oracle, across GQA ratios, page
    sizes, partial last pages and inactive (q_len = 0) rows."""
    rng = np.random.default_rng(hq * 100 + hkv * 10 + ps)
    B, dh, n_pages, max_pages = 3, 32, 12, 3
    q = _mk((B, 1, hq, dh), jnp.float32, seed=ps + hq)
    kp = _mk((1, n_pages, hkv, ps, dh), jnp.float32, seed=2)
    vp = _mk((1, n_pages, hkv, ps, dh), jnp.float32, seed=3)
    bt = jnp.asarray(rng.permutation(n_pages)[:B * max_pages]
                     .reshape(B, max_pages), jnp.int32)
    # the token at ctx attends positions <= ctx: ps + 4 and all
    # max_pages * ps tokens; row 2 is inactive
    ctx = jnp.asarray([ps + 3, max_pages * ps - 1, 0], jnp.int32)
    q_len = jnp.asarray([1, 1, 0], jnp.int32)
    args = (q, kp, vp, bt, ctx, q_len)
    want = ops.paged_decode_ragged(*args, layer=0, impl="ref")
    got = ops.paged_decode_ragged(*args, layer=0, impl="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.max(jnp.abs(got[2]))) == 0.0     # inactive row


def test_paged_kv_write_scatter_and_masking():
    """paged_kv_write places token (b, i) at (block_table[b, p//ps],
    p % ps) of the layer it is given and skips rows past n_valid instead
    of writing them."""
    from repro.nn.attention import paged_kv_write
    ps, n_pages, hkv, dh = 4, 6, 2, 8
    kp = jnp.zeros((2, n_pages, hkv, ps, dh), jnp.float32)
    vp = jnp.zeros_like(kp)
    k_new = _mk((1, 3, hkv, dh), jnp.float32, seed=4)
    v_new = _mk((1, 3, hkv, dh), jnp.float32, seed=5)
    bt = jnp.asarray([[5, 2, 0]], jnp.int32)
    ctx = jnp.asarray([3], jnp.int32)        # page 0 last slot, page 1
    n_valid = jnp.asarray([2], jnp.int32)    # third token masked
    for impl in ("ref", "interpret"):
        kp2, vp2 = paged_kv_write(kp, vp, k_new, v_new, 1, bt, ctx,
                                  n_valid, impl=impl)
        np.testing.assert_allclose(np.asarray(kp2[1, 5, :, 3]),
                                   np.asarray(k_new[0, 0]))
        np.testing.assert_allclose(np.asarray(vp2[1, 2, :, 0]),
                                   np.asarray(v_new[0, 1]))
        assert float(jnp.abs(kp2[1, 2, :, 1]).max()) == 0.0   # skipped
        assert float(jnp.abs(kp2[0]).max()) == 0.0      # other layer


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("ps", [8, 32])
@pytest.mark.parametrize("scheme", [None, "spx_8_x3"], ids=["f32", "spx"])
def test_paged_kv_write_kernel_bit_identical_to_ref(scheme, ps, layer):
    """The in-place write kernel (interpret mode) leaves every leaf of the
    stacked pools — f32 pages, or SPx codes and their lane-major scales —
    bit-identical to the XLA scatter, with windows that cross page
    boundaries, a window that ends mid-page, an inactive slot, and the
    first and last layer of three."""
    from repro.nn.attention import paged_kv_write, quantize_pages
    rng = np.random.default_rng(ps + layer)
    n_layers, b, hkv, dh, max_pages = 3, 4, 2, 32, 4
    n_pages = b * max_pages + 2
    c = ps + 5                                  # spans up to 3 pages

    def pool():
        x = _mk((n_layers, n_pages, hkv, ps, dh), jnp.float32,
                seed=int(rng.integers(1 << 20)))
        return x if scheme is None else quantize_pages(x, scheme)

    kp, vp = pool(), pool()
    k_new = _mk((b, c, hkv, dh), jnp.float32, seed=ps)
    v_new = _mk((b, c, hkv, dh), jnp.float32, seed=ps + 1)
    bt = jnp.asarray(rng.permutation(n_pages)[:b * max_pages]
                     .reshape(b, max_pages), jnp.int32)
    ctx = jnp.asarray([0, ps - 3, 2 * ps + 1, 7], jnp.int32)
    n_valid = jnp.asarray([c, 5, 0, c - 2], jnp.int32)
    kw = dict(kv_scheme=scheme) if scheme else {}
    want = paged_kv_write(kp, vp, k_new, v_new, layer, bt, ctx, n_valid,
                          impl="ref", **kw)
    ops.reset_op_calls()
    got = paged_kv_write(kp, vp, k_new, v_new, layer, bt, ctx, n_valid,
                         impl="interpret", **kw)
    assert ops.op_impls() == {("paged_kv_write", "interpret"): 1}
    for g, w, old in zip(jax.tree_util.tree_leaves(got),
                         jax.tree_util.tree_leaves(want),
                         jax.tree_util.tree_leaves((kp, vp))):
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes()
        assert not np.array_equal(np.asarray(g), np.asarray(old))


@pytest.mark.parametrize("scheme", [None, "spx_8_x3"], ids=["f32", "spx"])
def test_ragged_decode_reads_the_stacked_pool_in_place(scheme):
    """Both ragged decode kernels, given every layer's pools and a layer
    index, return bit for bit what they return for that layer's pools
    alone, at the first and the last layer; so does the oracle."""
    from repro.nn.attention import quantize_pages
    rng = np.random.default_rng(9)
    n_layers, b, hq, hkv, dh, ps, mp, w = 3, 3, 4, 2, 32, 8, 3, 2
    n_pages = b * mp + 1

    def pool():
        x = jnp.asarray(rng.standard_normal((n_layers, n_pages, hkv, ps,
                                             dh)), jnp.float32)
        return x if scheme is None else quantize_pages(x, scheme)

    kp, vp = pool(), pool()
    q = jnp.asarray(rng.standard_normal((b, w, hq, dh)), jnp.float32)
    bt = jnp.asarray(rng.integers(0, n_pages, (b, mp)), jnp.int32)
    ctx = jnp.asarray([0, ps - 1, 2 * ps + 3], jnp.int32)
    qlen = jnp.asarray([0, 2, 1], jnp.int32)
    kw = dict(kv_scheme=scheme) if scheme else {}
    for layer in (0, n_layers - 1):
        one = jax.tree_util.tree_map(lambda a: a[layer:layer + 1], (kp, vp))
        for impl in ("interpret", "ref"):
            got = ops.paged_decode_ragged(q, kp, vp, bt, ctx, qlen,
                                          layer=layer, impl=impl, **kw)
            want = ops.paged_decode_ragged(q, *one, bt, ctx, qlen,
                                           layer=0, impl=impl, **kw)
            assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
