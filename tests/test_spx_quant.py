"""Property + unit tests for SPx quantization (paper §3.2, Eq. 3.1/3.3/3.4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import spx
from repro.core.quantized import dequantize, quantize_weight, ref_matmul

jax.config.update("jax_platform_name", "cpu")


# ---------------------------------------------------------------------------
# Level-set structure (Eq. 3.1 / 3.3 / 3.4)
# ---------------------------------------------------------------------------

class TestLevelSets:
    @pytest.mark.parametrize("b", [2, 3, 4])
    def test_pot_levels_match_eq31(self, b):
        lv = spx.pot_levels(b)
        # Eq 3.1: {0} ∪ {±2^-e : e = 0..2^(b-1)-1}
        expect = {0.0} | {s * 0.5 ** e for e in range(2 ** (b - 1)) for s in (1, -1)}
        assert set(np.round(lv, 12)) == {round(v, 12) for v in expect}

    @pytest.mark.parametrize("tb", [(1,), (2, 1), (3, 3), (1, 1, 1), (2, 2, 1)])
    def test_spx_symmetric_sorted_normalized(self, tb):
        lv = spx.spx_levels(tb)
        assert np.all(np.diff(lv) > 0), "levels strictly sorted"
        np.testing.assert_allclose(lv, -lv[::-1], atol=0)
        assert 0.0 in lv and lv[-1] == 1.0 and lv[0] == -1.0

    def test_sp2_refines_pot_tail(self):
        """The paper's motivation: PoT is sparse near ±alpha; SP2 is denser.
        Compare the largest gap in the tail region [0.5, 1.0]."""
        def max_tail_gap(lv):
            tail = lv[lv >= 0.5]
            return np.max(np.diff(tail))
        pot = spx.pot_levels(4)
        sp2 = spx.sp2_levels(4)
        assert max_tail_gap(sp2) < max_tail_gap(pot)

    def test_spx_x3_refines_sp2_tail(self):
        """Eq. 3.4's extension: at matched code width (8 bits), x=3 places a
        larger FRACTION of its levels in the tail [0.5, 1] than SP2 — the
        'more choices at the two tail ends' the paper claims."""
        sp2 = spx.scheme_levels("sp2_8")      # (4,2), width 8
        sp3 = spx.scheme_levels("spx_8_x3")   # (3,2,2), width 8
        assert spx.code_width(sp2) == spx.code_width(sp3) == 8
        def tail_frac(lv):
            return np.sum((lv >= 0.5) & (lv <= 1.0)) / len(lv)
        assert tail_frac(sp3) > tail_frac(sp2)

    def test_code_width_all_schemes_le_8(self):
        for name in spx.SCHEMES:
            lv = spx.scheme_levels(name)
            assert spx.code_width(lv) <= 8, name

    def test_codebook_padded_pow2(self):
        for name in spx.SCHEMES:
            lut = spx.codebook(spx.scheme_levels(name))
            n = lut.shape[0]
            assert n & (n - 1) == 0


# ---------------------------------------------------------------------------
# Quantize/dequantize properties (hypothesis)
# ---------------------------------------------------------------------------

SCHEME_NAMES = sorted(spx.SCHEMES)


@st.composite
def arrays(draw, max_size=64):
    n = draw(st.integers(2, max_size))
    seed = draw(st.integers(0, 2**31 - 1))
    scale = draw(st.floats(1e-3, 1e3))
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(n) * scale).astype(np.float32)


class TestQuantizeProps:
    @settings(max_examples=40, deadline=None)
    @given(x=arrays(), scheme=st.sampled_from(SCHEME_NAMES))
    def test_error_bounded_by_half_max_gap(self, x, scheme):
        lv = spx.scheme_levels(scheme)
        alpha = spx.calibrate_minmax(jnp.asarray(x), channel_axis=None)
        xh = spx.fake_quantize(jnp.asarray(x), scheme, alpha)
        gap = np.max(np.diff(lv))
        err = np.abs(np.asarray(xh) - x)
        a = np.asarray(alpha).item()
        assert np.all(err <= a * gap / 2 + 1e-5 * a)

    @settings(max_examples=25, deadline=None)
    @given(x=arrays(), scheme=st.sampled_from(SCHEME_NAMES))
    def test_idempotent(self, x, scheme):
        """quantize(dequantize(quantize(x))) == quantize(x)."""
        alpha = spx.calibrate_minmax(jnp.asarray(x), channel_axis=None)
        lv = spx.scheme_levels(scheme)
        c1 = spx.quantize_to_codes(jnp.asarray(x), lv, alpha)
        xh = spx.dequantize_codes(c1, spx.codebook(lv), alpha, dtype=jnp.float32)
        c2 = spx.quantize_to_codes(xh, lv, alpha)
        np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))

    @settings(max_examples=25, deadline=None)
    @given(x=arrays())
    def test_levels_are_fixed_points(self, x):
        """Exact level values quantize to themselves."""
        lv = spx.scheme_levels("sp2_4")
        vals = jnp.asarray(lv, jnp.float32)
        xh = spx.fake_quantize(vals, "sp2_4", jnp.asarray(1.0))
        np.testing.assert_allclose(np.asarray(xh), lv, atol=1e-6)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1))
    def test_mse_calibration_not_worse_than_minmax(self, seed):
        rng = np.random.default_rng(seed)
        w = rng.standard_normal((32, 48)).astype(np.float32)
        w = jnp.asarray(w)
        scheme = "sp2_4"
        a_mm = spx.calibrate_minmax(w, -1)
        a_mse = spx.calibrate_mse(w, scheme, -1)
        e_mm = jnp.mean((spx.fake_quantize(w, scheme, a_mm) - w) ** 2)
        e_mse = jnp.mean((spx.fake_quantize(w, scheme, a_mse) - w) ** 2)
        assert float(e_mse) <= float(e_mm) * (1 + 1e-6)

    @pytest.mark.parametrize("scheme", ["sp2_4", "spx_8_x3"])
    def test_mse_calibration_is_the_grid_argmin(self, scheme):
        """Per channel, the first grid fraction of max|w| whose rounding
        error is least (the calibration walks the grid one fraction at a
        time to bound its temporaries)."""
        rng = np.random.default_rng(7)
        w = jnp.asarray(rng.standard_t(3, (64, 40)).astype(np.float32))
        base = np.maximum(np.abs(np.asarray(w)).max(0, keepdims=True), 1e-12)
        fracs = np.linspace(0.4, 1.05, 24).astype(np.float32)
        errs = [np.asarray(jnp.sum((spx.fake_quantize(
            w, scheme, jnp.asarray(base * f)) - w) ** 2, 0)) for f in fracs]
        want = base * fracs[np.argmin(np.stack(errs), 0)]
        got = spx.calibrate_mse(w, scheme, axes=(-2,))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)

    @settings(max_examples=30, deadline=None)
    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 32))
    def test_pack_unpack_roundtrip(self, seed, n):
        rng = np.random.default_rng(seed)
        codes = jnp.asarray(rng.integers(0, 16, size=(3, 2 * n)), jnp.uint8)
        np.testing.assert_array_equal(
            np.asarray(spx.unpack_int4(spx.pack_int4(codes))), np.asarray(codes))


def test_pack_int4_lane_groups():
    """Column c of each 256-column group shares a byte with column c+128
    (low / high nibble), so a kernel unpacks a tile by concatenating two
    lane-aligned halves; a dim with no 256 multiple is one group."""
    rng = np.random.default_rng(5)
    codes = rng.integers(0, 16, size=(2, 512)).astype(np.uint8)
    packed = np.asarray(spx.pack_int4(jnp.asarray(codes)))
    assert packed.shape == (2, 256)
    for g in range(2):
        blk = packed[:, 128 * g:128 * (g + 1)]
        np.testing.assert_array_equal(blk & 0x0F,
                                      codes[:, 256 * g:256 * g + 128])
        np.testing.assert_array_equal(blk >> 4,
                                      codes[:, 256 * g + 128:256 * (g + 1)])
    small = rng.integers(0, 16, size=(3, 96)).astype(np.uint8)
    sp = np.asarray(spx.pack_int4(jnp.asarray(small)))
    np.testing.assert_array_equal(sp & 0x0F, small[:, :48])
    np.testing.assert_array_equal(sp >> 4, small[:, 48:])


# ---------------------------------------------------------------------------
# QuantizedTensor + ref matmul
# ---------------------------------------------------------------------------

class TestQuantizedTensor:
    def test_roundtrip_and_storage(self):
        rng = np.random.default_rng(0)
        w = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
        qt = quantize_weight(w, "sp2_4")
        assert qt.packed and qt.codes.shape == (64, 48)
        assert qt.nbytes_stored() < w.size * 4 / 6  # >6x smaller than f32
        wh = dequantize(qt, jnp.float32)
        rel = float(jnp.linalg.norm(wh - w) / jnp.linalg.norm(w))
        assert rel < 0.25  # 4-bit nonuniform: coarse but sane

    @pytest.mark.parametrize("scheme", ["sp2_4", "spx_8_x3"])
    def test_stacked_quantize_matches_whole_stack(self, scheme):
        """Quantizing a layer stack one matrix at a time gives exactly the
        codes and scales of calibrating the whole stack at once."""
        rng = np.random.default_rng(6)
        w = jnp.asarray(rng.standard_normal((3, 64, 256)) * 0.05,
                        jnp.float32)
        qt = quantize_weight(w, scheme)
        scale = spx.calibrate_mse(w, scheme, axes=(-2,))
        codes = spx.quantize_to_codes(w, spx.scheme_levels(scheme), scale)
        if qt.packed:
            codes = spx.pack_int4(codes)
        np.testing.assert_array_equal(np.asarray(qt.scale),
                                      np.asarray(scale))
        np.testing.assert_array_equal(np.asarray(qt.codes),
                                      np.asarray(codes))

    def test_quantize_params_leaves_quantized_weights_alone(self):
        """Quantizing an already-quantized tree changes nothing, even where
        a stacked weight's scale is itself large enough to quantize."""
        from repro.nn.layers import quantize_params
        rng = np.random.default_rng(7)
        params = {"mlp": {"w": jnp.asarray(
            rng.standard_normal((2, 64, 4096)) * 0.05, jnp.float32)}}
        once = quantize_params(params, "sp2_4")
        assert once["mlp"]["w"].scale.size >= 4096
        twice = quantize_params(once, "sp2_4")
        assert isinstance(twice["mlp"]["w"].scale, jax.Array)
        for a, b in zip(jax.tree_util.tree_leaves(once),
                        jax.tree_util.tree_leaves(twice)):
            assert a is b

    def test_8bit_tighter_than_4bit(self):
        rng = np.random.default_rng(1)
        w = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
        def rel(scheme):
            qt = quantize_weight(w, scheme)
            return float(jnp.linalg.norm(dequantize(qt, jnp.float32) - w)
                         / jnp.linalg.norm(w))
        assert rel("sp2_8") < rel("sp2_4")

    def test_ref_matmul_matches_dequant_matmul(self):
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
        qt = quantize_weight(w, "sp2_8")
        got = ref_matmul(x, qt)
        want = x @ dequantize(qt, jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    def test_pytree_flattens_through_jit(self):
        rng = np.random.default_rng(3)
        w = jnp.asarray(rng.standard_normal((32, 32)), jnp.float32)
        qt = quantize_weight(w, "sp2_4")
        f = jax.jit(lambda x, q: ref_matmul(x, q))
        x = jnp.asarray(rng.standard_normal((4, 32)), jnp.float32)
        out = f(x, qt)
        assert out.shape == (4, 32)

    def test_quantized_matmul_snr(self):
        """End metric the paper cares about: matmul output fidelity."""
        rng = np.random.default_rng(4)
        x = jnp.asarray(rng.standard_normal((16, 256)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((256, 128)) * 0.05, jnp.float32)
        ref = x @ w
        for scheme, min_snr in [("sp2_8", 25.0), ("spx_8_x3", 25.0),
                                ("sp2_4", 8.0)]:
            qt = quantize_weight(w, scheme)
            out = ref_matmul(x, qt, out_dtype=jnp.float32)
            err = jnp.linalg.norm(out - ref)
            snr = 20 * jnp.log10(jnp.linalg.norm(ref) / (err + 1e-12))
            assert float(snr) > min_snr, (scheme, float(snr))


class TestPipelinePlan:
    def test_plan_fits_vmem_and_aligned(self):
        from repro.core import plan_matmul_blocks, TPU_V5E
        p = plan_matmul_blocks(4096, 4096, 4096, weight_bits=4)
        assert p.vmem_bytes <= TPU_V5E.vmem_bytes
        assert p.bm % 128 == 0 and p.bn % 128 == 0 and p.bk % 128 == 0

    def test_quantization_widens_pipeline_margin(self):
        """The two paper contributions compose: fewer weight bits -> load
        time shrinks -> pipeline margin grows (§3.1 condition easier)."""
        from repro.core import plan_matmul_blocks
        m16 = plan_matmul_blocks(8192, 8192, 8192, weight_bits=16)
        m4 = plan_matmul_blocks(8192, 8192, 8192, weight_bits=4)
        assert m4.margin >= m16.margin
