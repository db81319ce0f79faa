"""Pallas TPU kernels: ragged decode-window attention over a paged KV
cache, one launch per decode tick for plain decode and the speculative
verify window alike.

Serving's decode step is the paper's §3.1 pipeline with a twist: the K/V
"matrix" is no longer contiguous — it is scattered across fixed-size pages
owned by the sequence (see serving/kv_cache.py). The block table is a
*scalar-prefetch* argument (pltpu.PrefetchScalarGridSpec), so the physical
page index is known to the DMA engine before the grid step runs: the
gather happens in the BlockSpec index_map, and the inner loop is the same
double-buffered stream-pages-while-MXU-works pipeline as flash attention —
one (K, V) page pair in flight per (sequence, KV head) while the current
page's QK^T/PV runs, with running (m, l) softmax statistics in VMEM
scratch.

Grid: (B, Hkv, max_pages), pages innermost. GQA is handled by blocking the
query as (rep * w, dh) per KV head — the ``rep`` query heads that share a
KV head, each with its ``w`` window rows, ride in one block and reuse the
streamed page. Pages past a slot's attend length are skipped (pl.when),
and positions past each row's causal limit inside the last page are
masked; unused block-table slots point at page 0, whose DMA is wasted but
whose values are never read.

The kernels take every layer's pools stacked on a leading axis plus the
layer index as one more scalar prefetch, so the serving layer scan never
slices a layer out.

Page size comes from ``runtime.planner.plan_kv_pages`` — the same
VMEM-budget model the matmul tiles use.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from .decode import lut_decode, lut_operand

__all__ = ["paged_decode_ragged_pallas", "paged_decode_ragged_quant_pallas"]

_NEG_INF = -1e30


def _init_stats(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _token_scales(s_ref, h, page_size: int):
    """KV head ``h``'s per-token scales, (1, page_size), from its page's
    (1, Hkv, lanes) scale block (lane t = token t)."""
    return s_ref[0, pl.ds(h, 1), pl.ds(0, page_size)]


def _finalize_out(o_ref, m_ref, l_ref, acc_ref, out_dtype):
    # ctx == 0 rows (inactive slots) never ran a page: l == 0, out == 0
    denom = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0, 0] = (acc_ref[...] / denom).astype(out_dtype)


# ---------------------------------------------------------------------------
# Ragged decode megakernel: one launch per decode tick (plain decode AND the
# spec-decode K+1 verify window ride the same ragged (slot, attend_len) grid)
# ---------------------------------------------------------------------------

def _ragged_page_update(q, k, v, ctx, qn, j, *, scale, page_size, w,
                        m_ref, l_ref, acc_ref, k_scale=None, v_scale=None):
    """One page's contribution for a ragged decode *window*: the query
    block is (rep * w, dh) — w window rows per query head, rep-major —
    and the causal mask is per-row: window position ``i = row % w``
    attends positions <= ctx + i. Rows past ``qn`` (ragged window tails,
    inactive slots) are masked entirely and their probabilities zeroed,
    so l stays 0 and the finalize step emits exact zeros for them —
    matching the ref oracle bit-for-bit in interpret mode."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if k_scale is not None:
        s = s * k_scale
    rows = q.shape[0]
    win = jax.lax.broadcasted_iota(jnp.int32, (rows, page_size), 0) % w
    pos = j * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (rows, page_size), 1)
    mask = (pos <= ctx + win) & (win < qn)
    s = jnp.where(mask, s, _NEG_INF)

    m_prev = m_ref[...]                # (rows, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    # fully-masked rows keep m == _NEG_INF, where exp(s - m) would be 1 —
    # the explicit zeroing keeps their l/acc at exactly 0
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    pv = p if v_scale is None else p * v_scale
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        pv.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _ragged_kernel(bt_ref, ctx_ref, qn_ref, layer_ref, q_ref, k_ref, v_ref,
                   o_ref, m_ref, l_ref, acc_ref, *, scale: float,
                   page_size: int, w: int, n_logical: int, out_dtype):
    del bt_ref, layer_ref         # consumed by the BlockSpec index maps
    b = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_stats(m_ref, l_ref, acc_ref)

    ctx = ctx_ref[b]
    qn = qn_ref[b]

    # per-slot trip count: the page loop runs while this slot still has
    # attendable tokens (ctx + qn = its ragged attend_len) — no pow2
    # window padding, inactive slots (ctx == qn == 0) skip every page
    @pl.when(j * page_size < ctx + qn)
    def _page():
        _ragged_page_update(q_ref[0, 0], k_ref[0, 0], v_ref[0, 0], ctx, qn,
                            j, scale=scale, page_size=page_size, w=w,
                            m_ref=m_ref, l_ref=l_ref, acc_ref=acc_ref)

    @pl.when(j == n_logical - 1)
    def _finalize():
        _finalize_out(o_ref, m_ref, l_ref, acc_ref, out_dtype)


def _ragged_quant_kernel(bt_ref, ctx_ref, qn_ref, layer_ref, q_ref, kc_ref,
                         ks_ref, vc_ref, vs_ref, lut_ref, o_ref, m_ref, l_ref,
                         acc_ref, *, scale: float, page_size: int, w: int,
                         n_logical: int, out_dtype):
    """Fused-LUT ragged megakernel: K/V pages stream as uint8 codes +
    per-token scale, the <=256-entry codebook stays resident for the whole
    grid, and dequantization happens page-by-page right before the MXU —
    the §3.2 memory win on the one launch the decode tick makes."""
    del bt_ref, layer_ref
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        _init_stats(m_ref, l_ref, acc_ref)

    ctx = ctx_ref[b]
    qn = qn_ref[b]

    @pl.when(j * page_size < ctx + qn)
    def _page():
        k = lut_decode(kc_ref[0, 0].astype(jnp.int32), lut_ref)
        v = lut_decode(vc_ref[0, 0].astype(jnp.int32), lut_ref)
        _ragged_page_update(q_ref[0, 0], k, v, ctx, qn, j, scale=scale,
                            page_size=page_size, w=w, m_ref=m_ref,
                            l_ref=l_ref, acc_ref=acc_ref,
                            k_scale=_token_scales(ks_ref, h, page_size),
                            v_scale=_token_scales(vs_ref, h, page_size))

    @pl.when(j == n_logical - 1)
    def _finalize():
        _finalize_out(o_ref, m_ref, l_ref, acc_ref, out_dtype)


@functools.partial(jax.jit,
                   static_argnames=("w", "out_dtype", "interpret"))
def paged_decode_ragged_pallas(q, k_pages, v_pages, block_table, ctx_len,
                               q_len, layer, *, w: int, out_dtype=None,
                               interpret: bool = False):
    """Ragged decode-window attention in one launch.

    q: (B, Hkv, R, dh) with R = rep * w window rows per KV head
    (rep-major: row ``r * w + i`` is window position i of query head r);
    ``w`` is the static window length (spec K+1; 1 = plain decode);
    q_len: (B,) int32 valid rows per slot (ragged; rows past it come back
    zero); ctx_len: (B,) int32 tokens in the pages before the window.
    k_pages/v_pages: every layer's pools, (P, n_pages, Hkv, page_size,
    dh); layer: (1,) int32, the layer read, scalar-prefetched into the
    page index maps. block_table: (B, max_pages) int32, the physical
    page of each logical page. Returns (B, Hkv, R, dh).
    """
    b, hkv, rows, dh = q.shape
    page_size = k_pages.shape[3]
    max_pages = block_table.shape[1]
    out_dtype = out_dtype or q.dtype
    scale = 1.0 / (dh ** 0.5)

    page = pl.BlockSpec((None, 1, 1, page_size, dh),
                        lambda bb, h, j, bt, ctx, qn, l:
                        (l[0], bt[bb, j], h, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,        # block_table, ctx_len, q_len, layer
        grid=(b, hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rows, dh),
                         lambda bb, h, j, bt, ctx, qn, l: (bb, h, 0, 0)),
            page,
            page,
        ],
        out_specs=pl.BlockSpec((1, 1, rows, dh),
                               lambda bb, h, j, bt, ctx, qn, l:
                               (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),     # running max m
            pltpu.VMEM((rows, 1), jnp.float32),     # running denom l
            pltpu.VMEM((rows, dh), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_kernel, scale=scale, page_size=page_size,
                          w=w, n_logical=max_pages, out_dtype=out_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, dh), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, ctx_len, q_len, layer, q, k_pages, v_pages)


@functools.partial(jax.jit,
                   static_argnames=("w", "out_dtype", "interpret"))
def paged_decode_ragged_quant_pallas(q, k_codes, k_scale, v_codes, v_scale,
                                     block_table, ctx_len, q_len, layer, lut,
                                     *, w: int, out_dtype=None,
                                     interpret: bool = False):
    """Fused-LUT ragged decode window over quantized (codes + scale) KV
    pools: same grid and per-row causal masking as
    ``paged_decode_ragged_pallas``, but each streamed page is 1-byte
    codes + per-token scale, dequantized in VMEM against the resident
    codebook before the MXU. k_codes/v_codes: (P, n_pages, Hkv,
    page_size, dh) uint8; k_scale/v_scale: (P, n_pages, Hkv, lanes) f32,
    lane t the scale of the page's token t; lut: the KV scheme's f32
    codebook (at most 256 entries); other args as
    ``paged_decode_ragged_pallas``. Returns (B, Hkv, R, dh)."""
    b, hkv, rows, dh = q.shape
    page_size = k_codes.shape[3]
    lanes = k_scale.shape[3]
    max_pages = block_table.shape[1]
    out_dtype = out_dtype or q.dtype
    scale = 1.0 / (dh ** 0.5)

    codes = pl.BlockSpec((None, 1, 1, page_size, dh),
                         lambda bb, h, j, bt, ctx, qn, l:
                         (l[0], bt[bb, j], h, 0, 0))
    scales = pl.BlockSpec((None, 1, hkv, lanes),
                          lambda bb, h, j, bt, ctx, qn, l:
                          (l[0], bt[bb, j], 0, 0))
    lut, lut_spec = lut_operand(lut, dh, "paged_decode_ragged_quant")

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,        # block_table, ctx_len, q_len, layer
        grid=(b, hkv, max_pages),
        in_specs=[
            pl.BlockSpec((1, 1, rows, dh),
                         lambda bb, h, j, bt, ctx, qn, l: (bb, h, 0, 0)),
            codes,                         # k codes
            scales,                        # k scale
            codes,                         # v codes
            scales,                        # v scale
            lut_spec,                      # whole LUT, resident
        ],
        out_specs=pl.BlockSpec((1, 1, rows, dh),
                               lambda bb, h, j, bt, ctx, qn, l:
                               (bb, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),     # running max m
            pltpu.VMEM((rows, 1), jnp.float32),     # running denom l
            pltpu.VMEM((rows, dh), jnp.float32),    # output accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_ragged_quant_kernel, scale=scale,
                          page_size=page_size, w=w, n_logical=max_pages,
                          out_dtype=out_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, dh), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(block_table, ctx_len, q_len, layer, q, k_codes, k_scale, v_codes,
      v_scale, lut)
