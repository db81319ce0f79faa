"""Scheme-parameterized KV-cache quantization (docs/QUANTIZATION.md):

* paged SPx-quantized serving produces greedy outputs matching the dense
  f32 engine on mixed-length batches (the tentpole acceptance),
* dense ``kv_quant`` decode logits stay within tolerance of the f32 cache
  (including the GQA ``jnp.repeat`` scale-folding path),
* the fused-dequant ragged decode kernel at W = 1 (interpret mode)
  matches the jnp oracle per scheme,
* pool/cache byte accounting equals the bytes actually allocated,
* SPx level-set edge cases (midpoint ties, codebook padding, sp2_8 uint8
  round-trip), pack_int4 odd-dim errors, PagePool.release errors.

No hypothesis dependency — collected on the bare tier-1 environment.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import spx
from repro.core.quantized import quantize_weight
from repro.kernels import ops
from repro.models import lm as lm_mod
from repro.nn.attention import dequantize_kv, quantize_kv, quantize_pages
from repro.runtime import Runtime, planner
from repro.serving.engine import Request, ServeConfig, ServeEngine
from repro.serving.kv_cache import (PagePool, kv_bytes_per_token, page_bytes,
                                    pool_bytes)

jax.config.update("jax_platform_name", "cpu")

RT = Runtime(impl="ref", q_chunk=16)


def _gqa_cfg():
    """Reduced granite: 4 query heads on 1 KV head -> rep=4, exercising
    the GQA repeat of codes AND scales in the decode score/value folds."""
    return reduced(get_config("granite-3-8b"))


def _serving_cfg():
    # vocab=32 keeps random-init top-2 logit gaps wide relative to the
    # ~2% SPx KV error (512-way random logits are mostly near-ties, which
    # would turn the greedy-equality assertion into a coin flip); dh=128
    # is a serving-realistic head width (see benchmarks/serving_bench.py).
    return dataclasses.replace(reduced(get_config("gemma-2b"), vocab=32),
                               head_dim=128)


# ---------------------------------------------------------------------------
# Tentpole acceptance: paged quantized serving == dense f32 greedy outputs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["uniform8", "spx_8_x3"])
def test_paged_quant_engine_matches_dense_f32(scheme):
    cfg = _serving_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (3, 9, 17, 6, 12)]

    def drive(layout, rt=RT, **kw):
        eng = ServeEngine(params, cfg,
                          ServeConfig(batch_slots=2, max_seq=32, quantize=None,
                                      kv_layout=layout, **kw),
                          rt=rt)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=5))
        return {r.rid: r.output for r in eng.run()}, eng

    dense, _ = drive("dense")
    quant, eng = drive("paged",
                       rt=RT.replace(kv_quant=True, kv_scheme=scheme),
                       prefill_chunk=8, page_size=16)
    assert eng.kv_layout == "paged" and eng.kv_scheme == scheme
    assert dense == quant, f"greedy divergence under {scheme} KV"
    m = eng.metrics()
    assert m["kv_scheme"] == scheme
    # quantized pages bill codes+scale bytes, not cache-dtype elements
    assert m["peak_kv_bytes"] > 0
    assert (m["peak_kv_bytes"]
            == eng.pool.stats.peak_pages_in_use
            * page_bytes(cfg, eng.page_size, kv_scheme=scheme))


def test_paged_quant_undercuts_bf16_pool_bytes():
    """The acceptance's memory axis at matched page geometry: an SPx page
    is codes+scale (dh + 4 bytes/token/head/side) vs bf16's 2*dh."""
    cfg = _serving_cfg()
    spx_tok = kv_bytes_per_token(cfg, kv_scheme="spx_8_x3")
    bf16_tok = kv_bytes_per_token(cfg, jnp.bfloat16)
    assert bf16_tok / spx_tok == pytest.approx(2 * 128 / (128 + 4))
    assert bf16_tok / spx_tok > 1.9


# ---------------------------------------------------------------------------
# Satellite: dense kv_quant routes through the same scheme path (regression
# pinning decode logits against the f32 cache, GQA rep=4)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme,atol",
                         [("uniform8", 0.1), ("spx_8_x3", 0.35),
                          ("sp2_8", 0.6)])
def test_dense_kv_quant_decode_close_to_f32(scheme, atol):
    cfg = _gqa_cfg()
    assert cfg.n_heads // cfg.n_kv_heads > 1     # GQA repeat path
    rtq = RT.replace(kv_quant=True, kv_scheme=scheme)
    params = lm_mod.lm_init(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, 9),
                       jnp.int32)[None, :]

    caches = lm_mod.init_caches(cfg, 1, 32, dtype=jnp.float32)
    fl, caches = lm_mod.lm_prefill(params, toks, caches, cfg, RT)
    qcaches = lm_mod.init_caches(cfg, 1, 32, dtype=jnp.float32,
                                 kv_quant=True)
    ql, qcaches = lm_mod.lm_prefill(params, toks, qcaches, cfg, rtq)
    # prefill attention runs on the pre-quantization K/V; only the cache
    # write is quantized, so prefill logits are identical
    np.testing.assert_allclose(np.asarray(fl), np.asarray(ql), atol=1e-5)

    pos, tok = 9, int(jnp.argmax(fl[0]))
    for _ in range(6):
        fl, caches = lm_mod.lm_decode_step(
            params, jnp.asarray([tok], jnp.int32), jnp.int32(pos),
            caches, cfg, RT)
        ql, qcaches = lm_mod.lm_decode_step(
            params, jnp.asarray([tok], jnp.int32), jnp.int32(pos),
            qcaches, cfg, rtq)
        np.testing.assert_allclose(np.asarray(fl), np.asarray(ql),
                                   atol=atol)
        assert int(jnp.argmax(fl[0])) == int(jnp.argmax(ql[0]))
        tok = int(jnp.argmax(fl[0]))
        pos += 1


def test_quantize_kv_uniform8_matches_legacy_int8():
    """uniform8 through the codebook path reproduces the old hand-rolled
    symmetric-int8 quantization (same 255 levels, same minmax scale)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 3, 32)), jnp.float32)
    codes, scale = quantize_kv(x, "uniform8")
    assert codes.dtype == jnp.uint8
    xh = dequantize_kv(codes, scale, "uniform8")
    legacy = (jnp.clip(jnp.round(x / scale * 127.0), -127, 127)
              .astype(jnp.int8).astype(jnp.float32) * scale / 127.0)
    np.testing.assert_allclose(np.asarray(xh), np.asarray(legacy),
                               atol=1e-6)


# ---------------------------------------------------------------------------
# Kernel parity: fused-dequant paged decode (interpret) vs jnp oracle
# ---------------------------------------------------------------------------

# dh=128 tiles take the lane-gather lookup, dh=16 the select chain
# (kernels/decode.py)
@pytest.mark.parametrize("dh", [16, 128])
@pytest.mark.parametrize("scheme", ["uniform8", "sp2_8", "spx_8_x3"])
def test_paged_quant_kernel_interpret_matches_ref(scheme, dh):
    rng = np.random.default_rng(0)
    b, hq, hkv, ps, n_pages, mp = 3, 4, 2, 8, 6, 2
    q = jnp.asarray(rng.standard_normal((b, 1, hq, dh)), jnp.float32)
    # one layer's pools, stacked as the decode kernel takes them
    kv = rng.standard_normal((2, 1, n_pages, hkv, ps, dh)).astype(np.float32)
    kp = quantize_pages(jnp.asarray(kv[0]), scheme)
    vp = quantize_pages(jnp.asarray(kv[1]), scheme)
    bt = jnp.asarray(rng.integers(0, n_pages, (b, mp)), jnp.int32)
    # inactive + partial pages: the token at ctx attends positions <= ctx
    ctx = jnp.asarray([0, 4, 12], jnp.int32)
    q_len = jnp.asarray([0, 1, 1], jnp.int32)
    args = (q, kp, vp, bt, ctx, q_len)
    ref = ops.paged_decode_ragged(*args, layer=0, kv_scheme=scheme,
                                  impl="ref")
    itp = ops.paged_decode_ragged(*args, layer=0, kv_scheme=scheme,
                                  impl="interpret")
    np.testing.assert_allclose(np.asarray(itp), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)
    assert np.all(np.asarray(ref[0]) == 0.0)     # inactive row: zeros


def test_plan_kv_pages_quant_geometry():
    """Quantized pages are sized for codes+scale bytes and floored at the
    uint8 sublane tile (32 tokens)."""
    planner.clear_plan_cache()
    qplan = planner.plan_kv_pages(1, 128, rep=8, kv_scheme="spx_8_x3")
    fplan = planner.plan_kv_pages(1, 128, rep=8, act_bytes=4)
    assert qplan.page_size >= 32
    assert fplan.page_size >= 8


# ---------------------------------------------------------------------------
# Satellite: byte accounting equals the arrays actually allocated
# ---------------------------------------------------------------------------

def _tree_nbytes(tree):
    return sum(l.nbytes for l in jax.tree_util.tree_leaves(tree))


@pytest.mark.parametrize("dtype,kv_quant",
                         [(jnp.float32, False), (jnp.bfloat16, False),
                          (jnp.float32, True)])
def test_pool_bytes_matches_allocated_nbytes(dtype, kv_quant):
    cfg = _gqa_cfg()
    n_pages, ps = 6, 8
    caches = lm_mod.paged_init_caches(cfg, n_pages, ps, dtype=dtype,
                                      kv_quant=kv_quant)
    scheme = "spx_8_x3" if kv_quant else None
    assert _tree_nbytes(caches) == pool_bytes(cfg, n_pages, ps, dtype,
                                              kv_scheme=scheme)


@pytest.mark.parametrize("kv_quant", [False, True], ids=["f32", "spx"])
def test_metrics_bill_the_bytes_the_pool_holds(kv_quant):
    """metrics() bills each page in use what the allocated pool holds for
    one page — its nbytes over n_pages, the SPx pool's lane-padded scale
    rows included — globally and per shard."""
    cfg = _serving_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    rt = RT.replace(kv_quant=True, kv_scheme="spx_8_x3") if kv_quant else RT
    eng = ServeEngine(params, cfg,
                      ServeConfig(batch_slots=2, max_seq=32, quantize=None,
                                  kv_layout="paged", page_size=8,
                                  prefill_chunk=8), rt=rt)
    rng = np.random.default_rng(0)
    for i, n in enumerate((5, 13)):
        eng.submit(Request(rid=i, max_new_tokens=3, prompt=rng.integers(
            0, cfg.vocab_size, n).astype(np.int32)))
    eng.run()
    m = eng.metrics()
    held = _tree_nbytes(eng.caches)
    assert held == pool_bytes(cfg, m["n_pages"], 8, jnp.float32,
                              kv_scheme="spx_8_x3" if kv_quant else None)
    assert m["peak_kv_pages"] > 0
    assert m["peak_kv_bytes"] * m["n_pages"] == m["peak_kv_pages"] * held
    assert m["pool_bytes_per_shard"] == held
    assert m["peak_kv_bytes_per_shard"] == m["peak_kv_bytes"]


@pytest.mark.parametrize("dtype,kv_quant",
                         [(jnp.float32, False), (jnp.bfloat16, False),
                          (jnp.float32, True)])
def test_dense_cache_bytes_match_kv_bytes_per_token(dtype, kv_quant):
    cfg = _gqa_cfg()
    b, s = 3, 16
    caches = lm_mod.init_caches(cfg, b, s, dtype=dtype, kv_quant=kv_quant)
    scheme = "uniform8" if kv_quant else None
    assert _tree_nbytes(caches) == b * s * kv_bytes_per_token(
        cfg, dtype, kv_scheme=scheme)


# ---------------------------------------------------------------------------
# Satellite: pack_int4 / quantize_weight on an odd last dim
# ---------------------------------------------------------------------------

def test_pack_int4_odd_last_dim_raises():
    codes = jnp.zeros((4, 7), jnp.uint8)
    with pytest.raises(ValueError, match="even last dim"):
        spx.pack_int4(codes)
    # explicit pack=True on an odd-width weight: clear error, not a
    # broadcast shape crash
    w = jnp.asarray(np.random.default_rng(0).standard_normal((8, 7)),
                    jnp.float32)
    with pytest.raises(ValueError, match="even last dim"):
        quantize_weight(w, "sp2_4", pack=True)
    # auto-pack declines odd dims and still round-trips
    qt = quantize_weight(w, "sp2_4")
    assert not qt.packed and qt.codes.shape == (8, 7)
    assert qt.dequantize().shape == (8, 7)
    # even dims auto-pack as before
    qt2 = quantize_weight(jnp.asarray(
        np.random.default_rng(1).standard_normal((8, 6)), jnp.float32),
        "sp2_4")
    assert qt2.packed and qt2.codes.shape == (8, 3)


# ---------------------------------------------------------------------------
# Satellite: PagePool.release error semantics + stats consistency
# ---------------------------------------------------------------------------

def test_page_pool_release_errors_and_stats_consistent():
    pool = PagePool(n_pages=4, page_size=8)
    assert pool.allocate(7, 20) is not None          # 3 pages
    # release of a never-admitted sequence: descriptive error, no stats
    # drift
    with pytest.raises(KeyError, match="never admitted"):
        pool.release(99)
    assert pool.stats.pages_in_use == 3
    assert pool.stats.release_calls == 0
    # normal release, then double release
    assert pool.release(7) == 3
    assert pool.stats.pages_in_use == 0
    assert pool.stats.release_calls == 1
    with pytest.raises(KeyError, match="double release"):
        pool.release(7)
    assert pool.stats.pages_in_use == 0
    assert pool.stats.release_calls == 1
    assert pool.free_pages() == 4
    # the pool still works after the error paths
    assert pool.allocate(8, 32) is not None
    assert pool.free_pages() == 0


# ---------------------------------------------------------------------------
# Satellite: SPx level-set edge cases
# ---------------------------------------------------------------------------

def test_quantize_to_codes_midpoint_tie_rounds_down():
    """A value exactly on the midpoint of two adjacent levels takes the
    LOWER level (searchsorted side='left' over midpoints) — pinned so a
    refactor to a different tie rule is a visible change."""
    levels = spx.scheme_levels("sp2_4")
    mids = (levels[1:] + levels[:-1]) / 2.0
    codes = spx.quantize_to_codes(jnp.asarray(mids, jnp.float32), levels,
                                  jnp.asarray(1.0))
    np.testing.assert_array_equal(np.asarray(codes),
                                  np.arange(len(levels) - 1))


@pytest.mark.parametrize("scheme", sorted(spx.SCHEMES))
def test_codebook_padding_never_emitted(scheme):
    """The pow2 codebook padding (repeats of the top level) must be
    unreachable from quantize: even +/-inf-magnitude inputs clip to the
    real level range."""
    levels = spx.scheme_levels(scheme)
    lut = spx.codebook(levels)
    x = jnp.asarray([-1e9, -1.0, 0.0, 1.0, 1e9], jnp.float32)
    codes = np.asarray(spx.quantize_to_codes(x, levels, jnp.asarray(1.0)))
    assert codes.max() == len(levels) - 1
    assert codes.max() < lut.shape[0] or len(levels) == lut.shape[0]
    # padding entries all repeat the top level
    np.testing.assert_array_equal(np.asarray(lut[len(levels):]),
                                  np.full(lut.shape[0] - len(levels),
                                          levels[-1], np.float32))


def test_sp2_8_roundtrips_through_uint8_codes():
    """179 levels fit uint8 with headroom: every exact level round-trips
    code -> value with no wraparound and no padding aliasing."""
    levels = spx.scheme_levels("sp2_8")
    assert len(levels) == 179
    vals = jnp.asarray(levels, jnp.float32)
    codes = spx.quantize_to_codes(vals, levels, jnp.asarray(1.0))
    assert codes.dtype == jnp.uint8
    np.testing.assert_array_equal(np.asarray(codes), np.arange(179))
    back = spx.dequantize_codes(codes, spx.codebook(levels),
                                jnp.asarray(1.0), dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(back), levels, atol=1e-7)
