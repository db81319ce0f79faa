"""Pure-jnp oracles for every Pallas kernel. These are the source of truth
for correctness tests (interpret-mode kernels must allclose against these)
and the implementation used on non-TPU backends and in the 512-device
dry-run (mathematically identical; XLA:TPU would fuse the dequant into the
matmul the same way the kernel does by hand)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import spx

__all__ = ["spx_matmul_ref", "spx_expert_matmul_ref", "attention_ref",
           "paged_attention_ref",
           "paged_attention_quant_ref", "paged_decode_ragged_ref",
           "paged_decode_ragged_quant_ref", "paged_kv_write_ref",
           "page_scales"]


def spx_matmul_ref(x, codes, scale, lut, *, packed: bool, out_dtype=None):
    """x:(..., K) @ (lut[codes:(K,N)] * scale:(1,N)) -> (..., N).
    Contracts x's LAST dim without flattening leading dims (their sharding
    must survive — see ops.spx_matmul)."""
    out_dtype = out_dtype or x.dtype
    if packed:
        codes = spx.unpack_int4(codes)
    w = jnp.take(lut, codes.astype(jnp.int32), axis=0)   # (K, N) in lut dtype
    acc = jax.lax.dot_general(
        x, w.astype(x.dtype), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (acc * scale).astype(out_dtype)


def spx_expert_matmul_ref(x, codes, scale, lut, tile_expert, tile_rows, *,
                          bm: int, packed: bool, out_dtype=None):
    """x: (G * bm, K) in G row tiles; tile g @ (lut[codes[tile_expert[g]]]
    * scale[tile_expert[g]]), zero where ``tile_rows[g]`` is 0. codes:
    (E, K, N) (or int4-packed (E, K, N/2)); scale: (E, 1, N). One tile's
    expert is dequantized at a time."""
    out_dtype = out_dtype or x.dtype
    g = tile_expert.shape[0]

    def tile(args):
        xt, e, rows = args
        y = spx_matmul_ref(xt, codes[e], scale[e], lut, packed=packed,
                           out_dtype=jnp.float32)
        return jnp.where(rows > 0, y, 0.0).astype(out_dtype)

    out = jax.lax.map(tile, (x.reshape(g, bm, x.shape[-1]), tile_expert,
                             tile_rows))
    return out.reshape(g * bm, out.shape[-1])


def paged_kv_write_ref(pools, rows, layer, block_table, ctx_len, n_valid):
    """Scatter rows ``i < n_valid[b]`` of each slot's window to positions
    ``ctx_len[b] + i`` of ``pools[k][layer]`` — the oracle of
    ``paged_kv_write_pallas`` (args as there). Rows past ``n_valid`` are
    dropped through an out-of-range page index."""
    b, c = rows[0].shape[:2]
    n_pages, page_size = pools[0].shape[1], pools[0].shape[3]
    positions = ctx_len[:, None] + jnp.arange(c, dtype=jnp.int32)
    phys = jnp.take_along_axis(
        block_table, jnp.clip(positions // page_size, 0,
                              block_table.shape[1] - 1), axis=1)
    phys = jnp.where(jnp.arange(c)[None, :] < n_valid[:, None], phys,
                     n_pages).reshape(-1)
    off = (positions % page_size).reshape(-1)
    out = []
    for pool, new in zip(pools, rows):
        flat = new.reshape((b * c,) + new.shape[2:]).astype(pool.dtype)
        at = (pool.at[layer, phys, :, off, :] if pool.ndim == 5
              else pool.at[layer, phys, :, off])
        out.append(at.set(flat, mode="drop"))
    return out


def page_scales(scale_pages, page_size: int):
    """Gathered scale pages (B, max_pages, Hkv, lanes), lane t the scale
    of the page's token t, as a contiguous (B, Hkv, S, 1) view."""
    b, mp, hkv = scale_pages.shape[:3]
    a = jnp.moveaxis(scale_pages[..., :page_size], 2, 1)
    return a.reshape(b, hkv, mp * page_size, 1)


def paged_attention_ref(q, k_pages, v_pages, block_table, ctx_len, *,
                        out_dtype=None):
    """Single-token decode attention over a paged KV cache.

    q: (B, Hkv, rep, dh) — query heads grouped under their KV head;
    k_pages/v_pages: (n_pages, Hkv, page_size, dh) physical page pools;
    block_table: (B, max_pages) int32 physical page per logical page;
    ctx_len: (B,) int32 — tokens attendable (positions < ctx_len).
    Returns (B, Hkv, rep, dh).

    Gathers this sequence's pages into a contiguous view and runs a plain
    max-shifted softmax in f32 — the oracle the Pallas kernel's online
    softmax must match.
    """
    out_dtype = out_dtype or q.dtype
    b, hkv, rep, dh = q.shape
    ps = k_pages.shape[2]
    max_pages = block_table.shape[1]
    s_max = max_pages * ps
    # gather: (B, max_pages, Hkv, ps, dh) -> (B, Hkv, S, dh)
    k = jnp.moveaxis(k_pages[block_table], 2, 1).reshape(b, hkv, s_max, dh)
    v = jnp.moveaxis(v_pages[block_table], 2, 1).reshape(b, hkv, s_max, dh)
    return _paged_softmax(q, k, v, ctx_len, out_dtype)


def _paged_softmax(q, k, v, ctx_len, out_dtype):
    """Shared masked-softmax core of the paged oracles. q: (B,Hkv,rep,dh);
    k/v: (B,Hkv,S,dh) contiguous gathered views."""
    dh = q.shape[-1]
    s_max = k.shape[2]
    s = jnp.einsum("bhrd,bhkd->bhrk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dh ** -0.5)
    pos = jnp.arange(s_max)
    s = jnp.where(pos[None, None, None, :] < ctx_len[:, None, None, None],
                  s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhrk,bhkd->bhrd", p, v.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30)
    # ctx == 0 rows (inactive slots): everything is masked and the shifted
    # softmax degenerates to a mean — force the kernel's all-zero output
    o = jnp.where(ctx_len[:, None, None, None] > 0, o, 0.0)
    return o.astype(out_dtype)


def paged_attention_quant_ref(q, k_codes, k_scale, v_codes, v_scale,
                              block_table, ctx_len, lut, *, out_dtype=None):
    """Quantized-pool variant of ``paged_attention_ref``: pools hold uint8
    codebook codes plus a per-token f32 scale, and dequantization
    (``lut[codes] * scale``) is fused after the page gather — the oracle
    the fused-dequant Pallas kernel must match.

    k_codes/v_codes: (n_pages, Hkv, page_size, dh) uint8; k_scale/v_scale:
    (n_pages, Hkv, lanes) f32, lane t the scale of the page's token t
    (``spx.kv_scale_lanes``); lut: (2^w,) f32 codebook
    (spx.codebook of the KV scheme). Other args as paged_attention_ref.
    """
    out_dtype = out_dtype or q.dtype
    b, hkv, rep, dh = q.shape
    ps = k_codes.shape[2]
    s_max = block_table.shape[1] * ps

    def gather_dequant(codes, scale):
        c = jnp.moveaxis(codes[block_table], 2, 1).reshape(b, hkv, s_max, dh)
        a = page_scales(scale[block_table], ps)
        return jnp.take(lut, c.astype(jnp.int32), axis=0) * a

    k = gather_dequant(k_codes, k_scale)
    v = gather_dequant(v_codes, v_scale)
    return _paged_softmax(q, k, v, ctx_len, out_dtype)


def _ragged_softmax(q, k, v, ctx_len, q_len, w: int, out_dtype):
    """Shared masked-softmax core of the ragged decode-window oracles.

    q: (B, Hkv, R, dh) with R = rep * w rows ordered rep-major — row
    ``r * w + i`` is window position ``i`` of the ``r``-th query head
    sharing this KV head; k/v: (B, Hkv, S, dh) contiguous gathered views.
    Window position ``i`` attends positions <= ctx_len + i (absolute
    causality inside the window); rows at positions >= q_len are padding
    and come back exactly zero.
    """
    dh = q.shape[-1]
    r_rows = q.shape[2]
    s_max = k.shape[2]
    s = jnp.einsum("bhrd,bhkd->bhrk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dh ** -0.5)
    win = jnp.arange(r_rows) % w                          # (R,)
    pos = jnp.arange(s_max)
    row_ok = win[None, None, :, None] < q_len[:, None, None, None]
    mask = (pos[None, None, None, :]
            <= ctx_len[:, None, None, None] + win[None, None, :, None]) \
        & row_ok
    s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhrk,bhkd->bhrd", p, v.astype(jnp.float32))
    o = o / jnp.maximum(l, 1e-30)
    # fully-masked rows (window padding, inactive slots) degenerate to a
    # mean under the shifted softmax — force the kernel's all-zero output
    o = jnp.where(row_ok, o, 0.0)
    return o.astype(out_dtype)


def paged_decode_ragged_ref(q, k_pages, v_pages, block_table, ctx_len,
                            q_len, *, w: int, out_dtype=None):
    """Ragged decode-window attention over a paged KV cache — the oracle
    for the decode megakernel (one launch covers plain decode *and* the
    spec-decode verify window).

    q: (B, Hkv, R, dh), R = rep * w query rows per KV head, rep-major (row
    ``r * w + i`` = window position i of query head r); ``w`` is the
    static window length (spec K+1, or 1 for plain decode); q_len: (B,)
    int32 valid window rows per slot (ragged — rows past it return zero);
    ctx_len: (B,) int32 tokens in the pages *before* this window (window
    position i attends positions <= ctx_len + i). k_pages/v_pages/
    block_table as in ``paged_attention_ref``. Returns (B, Hkv, R, dh).
    """
    out_dtype = out_dtype or q.dtype
    b, hkv, _, dh = q.shape
    ps = k_pages.shape[2]
    s_max = block_table.shape[1] * ps
    k = jnp.moveaxis(k_pages[block_table], 2, 1).reshape(b, hkv, s_max, dh)
    v = jnp.moveaxis(v_pages[block_table], 2, 1).reshape(b, hkv, s_max, dh)
    return _ragged_softmax(q, k, v, ctx_len, q_len, w, out_dtype)


def paged_decode_ragged_quant_ref(q, k_codes, k_scale, v_codes, v_scale,
                                  block_table, ctx_len, q_len, lut, *,
                                  w: int, out_dtype=None):
    """Quantized-pool variant of ``paged_decode_ragged_ref``: pools hold
    uint8 codebook codes + per-token f32 scale, dequantized after the page
    gather (``lut[codes] * scale``) — the oracle the fused-LUT megakernel
    must match. Args as ``paged_attention_quant_ref`` plus q_len/w."""
    out_dtype = out_dtype or q.dtype
    b, hkv, _, dh = q.shape
    ps = k_codes.shape[2]
    s_max = block_table.shape[1] * ps

    def gather_dequant(codes, scale):
        c = jnp.moveaxis(codes[block_table], 2, 1).reshape(b, hkv, s_max, dh)
        a = page_scales(scale[block_table], ps)
        return jnp.take(lut, c.astype(jnp.int32), axis=0) * a

    k = gather_dequant(k_codes, k_scale)
    v = gather_dequant(v_codes, v_scale)
    return _ragged_softmax(q, k, v, ctx_len, q_len, w, out_dtype)


def attention_ref(q, k, v, *, causal: bool = True, out_dtype=None):
    """Naive softmax attention. q:(BH,Sq,dh), k/v:(BH,Skv,dh)."""
    out_dtype = out_dtype or q.dtype
    dh = q.shape[-1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (dh ** 0.5)
    if causal:
        sq, skv = s.shape[-2], s.shape[-1]
        mask = (jnp.arange(sq)[:, None] >= jnp.arange(skv)[None, :])
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32))
    return out.astype(out_dtype)
