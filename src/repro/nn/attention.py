"""GQA attention: training/prefill (chunked online attention or Pallas flash
kernel) and decode (context-parallel flash-decode over a sequence-sharded KV
cache via shard_map).

Distribution notes
------------------
* Prefill/train: batch shards over data axes; the head dim of intermediates
  is constrained over the model axis (GSPMD pads uneven head counts — jit
  *inputs* are never unevenly sharded).
* Decode: the KV cache is a jit input, so its sharding must be even. KV head
  counts (1..16) generally aren't divisible by the 16-wide model axis, so the
  cache shards over the *sequence* dim instead, and attention runs as
  flash-decode context parallelism inside shard_map: each model-axis shard
  computes a local online-softmax partial + LSE stats; one tiny psum merges.
  The token's cache update lands in exactly one shard (clamped single-slot
  dynamic-update-slice — no collective, no full-cache copy).
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import spx
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.runtime import Runtime, registry

from .layers import dense_apply, dense_init
from .rotary import apply_mrope, apply_rope

__all__ = ["attn_init", "attn_apply_dense", "attention_core",
           "decode_attention", "attn_decode_step", "paged_kv_write",
           "attn_paged_step", "quantize_kv", "quantize_pages", "dequantize_kv",
           "kv_lut"]

_NEG = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def attn_init(key, d_model: int, n_heads: int, n_kv_heads: int,
              head_dim: int, *, qkv_bias: bool = False,
              dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], d_model, n_heads * head_dim, bias=qkv_bias,
                         dtype=dtype),
        "wk": dense_init(ks[1], d_model, n_kv_heads * head_dim, bias=qkv_bias,
                         dtype=dtype),
        "wv": dense_init(ks[2], d_model, n_kv_heads * head_dim, bias=qkv_bias,
                         dtype=dtype),
        "wo": dense_init(ks[3], n_heads * head_dim, d_model, dtype=dtype),
    }


def _project_qkv(p, x, n_heads, n_kv_heads, head_dim, rt):
    b, s, _ = x.shape
    q = dense_apply(p["wq"], x, rt).reshape(b, s, n_heads, head_dim)
    k = dense_apply(p["wk"], x, rt).reshape(b, s, n_kv_heads, head_dim)
    v = dense_apply(p["wv"], x, rt).reshape(b, s, n_kv_heads, head_dim)
    return q, k, v


def _apply_positional(q, k, positions, rope_theta, mrope_sections):
    """q and k rotated at ``positions`` (RoPE, or M-RoPE with
    ``mrope_sections``); ``rope_theta`` None means no positional
    encoding (NoPE), so both come back as they are."""
    if rope_theta is None:
        return q, k
    if mrope_sections is not None:
        # positions: (B, 3, S)
        q = apply_mrope(q, positions, sections=mrope_sections,
                        theta=rope_theta)
        k = apply_mrope(k, positions, sections=mrope_sections,
                        theta=rope_theta)
    else:
        q = apply_rope(q, positions, theta=rope_theta)
        k = apply_rope(k, positions, theta=rope_theta)
    return q, k


# ---------------------------------------------------------------------------
# Core attention (train / prefill)
# ---------------------------------------------------------------------------

def _chunked_attention(q, k, v, *, causal: bool, q_chunk: int,
                       unroll: bool = False, q_offset=0):
    """Memory-bounded attention: scan over query chunks; each chunk attends
    to the full key range with absolute-position causal masking. Scores are
    (B, H, cq, Skv) per step — never (S, S) — and only the per-chunk scores
    are f32; K/V stay bf16 and 4-D so the head dim keeps its model-axis
    sharding (no batch*head merge, which would force all-gathers). Pure jnp
    (CPU / dry-run path); the TPU path is the Pallas flash kernel."""
    b, h, sq, dh = q.shape
    skv = k.shape[2]
    cq = min(q_chunk, sq)
    if sq % cq:
        cq = sq  # ragged: single chunk (callers pass pow2 seqs)
    n_chunks = sq // cq
    scale = dh ** -0.5
    kv_pos = jnp.arange(skv)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def chunk(carry, i):
        # checkpointed: the (B, H, cq, Skv) probs are recomputed in the
        # backward (flash-attention-style) instead of being stacked across
        # the chunk scan — that stack is quadratic in S.
        q_i = jax.lax.dynamic_slice_in_dim(q, i * cq, cq, axis=2)
        s = jax.lax.dot_general(
            q_i, k, (((3,), (3,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32) * scale    # (B,H,cq,Skv)
        if causal:
            q_pos = q_offset + i * cq + jnp.arange(cq)
            s = jnp.where(q_pos[:, None] >= kv_pos[None, :], s, _NEG)
        p = jax.nn.softmax(s, axis=-1)
        o = jax.lax.dot_general(
            p.astype(v.dtype), v, (((3,), (2,)), ((0, 1), (0, 1))),
            preferred_element_type=jnp.float32)            # (B,H,cq,dh)
        return carry, o.astype(q.dtype)

    if n_chunks == 1:
        _, o = chunk(None, 0)
        return o
    _, outs = jax.lax.scan(chunk, None, jnp.arange(n_chunks),
                           unroll=True if unroll else 1)
    # outs: (nc, B, H, cq, dh) -> (B, H, Sq, dh)
    outs = jnp.moveaxis(outs, 0, 2)
    return outs.reshape(b, h, sq, dh)


def attention_core(q, k, v, *, causal: bool, rt: Runtime):
    """q: (B, Hq, Sq, dh); k, v: (B, Hkv, Skv, dh) -> (B, Hq, Sq, dh)."""
    if getattr(rt, "attn_cp", False) and rt.mesh is not None \
            and q.shape[2] % dict(rt.mesh.shape)[rt.model_axis] == 0 \
            and q.shape[2] == k.shape[2]:
        return _attention_core_cp(q, k, v, causal=causal, rt=rt)
    impl = registry.resolve("flash_attention", rt.impl).impl
    if impl in ("pallas", "interpret"):
        return ops.flash_attention(q, k, v, causal=causal, impl=impl,
                                   mesh=rt.mesh)
    b, hq, sq, dh = q.shape
    hkv = k.shape[1]
    rep = hq // hkv
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)   # bf16, head dim stays sharded
        v = jnp.repeat(v, rep, axis=1)
    return _chunked_attention(q, k, v, causal=causal, q_chunk=rt.q_chunk,
                              unroll=rt.unroll)


def _attention_core_cp(q, k, v, *, causal: bool, rt: Runtime):
    """Context-parallel attention (long-prefill path, §Perf cell 2):
    queries stay sequence-sharded over the model axis; each shard gathers
    only the (small, GQA) K/V and computes its causal rows locally. Per
    layer this moves S*Hkv*dh*2 bytes instead of the 3+ full-activation
    (S x d_model) gathers the TP/SP path needs — the difference between
    collective-bound and compute-bound 32k prefill."""
    axis = rt.model_axis
    n = dict(rt.mesh.shape)[axis]
    b, hq, sq, dh = q.shape
    s_loc = sq // n
    dp = rt.data_axes if rt.data_axes else None

    def local(q_l, k_g, v_g):
        # q_l: (B, Hq, S/n, dh); k_g/v_g: (B, Hkv, S, dh) replicated
        off = jax.lax.axis_index(axis) * s_loc
        rep = hq // k_g.shape[1]
        if rep > 1:
            k_g = jnp.repeat(k_g, rep, axis=1)
            v_g = jnp.repeat(v_g, rep, axis=1)
        return _chunked_attention(q_l, k_g, v_g, causal=causal,
                                  q_chunk=min(rt.q_chunk, s_loc),
                                  unroll=rt.unroll, q_offset=off)

    fn = jax.shard_map(
        local, mesh=rt.mesh,
        in_specs=(P(dp, None, axis, None), P(dp, None, None, None),
                  P(dp, None, None, None)),
        out_specs=P(dp, None, axis, None),
        check_vma=False)
    return fn(q, k, v)


def attn_apply_dense(p: dict, x: jax.Array, positions: jax.Array, *,
                     n_heads: int, n_kv_heads: int, head_dim: int,
                     causal: bool = True, rope_theta: float = 10000.0,
                     mrope_sections=None, rt: Runtime,
                     kv_out: bool = False,
                     cross_kv: tuple | None = None):
    """Full attention sublayer (projections + rope + core + output proj).

    cross_kv: optional (k, v) tuple — used by the enc-dec decoder's
    cross-attention (no rope on kv, not causal).
    Returns y or (y, (k, v)) if kv_out.
    """
    b, s, _ = x.shape
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, rt)
    if cross_kv is not None:
        k, v = cross_kv
        if mrope_sections is None and rope_theta is not None:
            q = apply_rope(q, positions, theta=rope_theta)
    elif positions is not None:
        q, k = _apply_positional(q, k, positions, rope_theta, mrope_sections)

    # sharding hints: TP mode shards heads over model (padded if uneven);
    # CP mode keeps q sequence-sharded (the KV gather happens in shard_map)
    if rt.mesh is not None and rt.model_axis is not None:
        from jax.sharding import NamedSharding
        dp = rt.data_axes if rt.data_axes else None
        if getattr(rt, "attn_cp", False):
            # CP: q/k/v all stay sequence-sharded through the projections
            # (compute stays 1/n per chip); the attention shard_map's
            # in_spec gathers only K/V at entry. Constraining k/v
            # "replicated" here instead makes GSPMD hoist the gather
            # before the projections — 16x replicated QKV/MLP compute
            # (measured: §Perf cell 2 iter 1).
            seq_spec = NamedSharding(rt.mesh, P(dp, rt.model_axis, None,
                                                None))
            q = jax.lax.with_sharding_constraint(q, seq_spec)
            k = jax.lax.with_sharding_constraint(k, seq_spec)
            v = jax.lax.with_sharding_constraint(v, seq_spec)
        else:
            q = jax.lax.with_sharding_constraint(
                q, NamedSharding(rt.mesh, P(dp, None, rt.model_axis, None)))
            k = jax.lax.with_sharding_constraint(
                k, NamedSharding(rt.mesh, P(dp, None, None, None)))
            v = jax.lax.with_sharding_constraint(
                v, NamedSharding(rt.mesh, P(dp, None, None, None)))

    qh = jnp.swapaxes(q, 1, 2)          # (B, Hq, S, dh)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    o = attention_core(qh, kh, vh, causal=causal and cross_kv is None, rt=rt)
    o = jnp.swapaxes(o, 1, 2).reshape(b, s, n_heads * head_dim)
    y = dense_apply(p["wo"], o, rt)
    if kv_out:
        return y, (k, v)
    return y


# ---------------------------------------------------------------------------
# Decode: context-parallel flash-decode over a seq-sharded KV cache
# ---------------------------------------------------------------------------

def kv_lut(scheme: str) -> jnp.ndarray:
    """f32 codebook LUT for a KV-cache scheme (pow2-padded; codes index it).
    Only 8-bit-code schemes are legal for the KV cache — the cache stores
    one uint8 code per element."""
    levels = spx.scheme_levels(scheme)
    if spx.code_width(levels) > 8:
        raise ValueError(f"KV scheme {scheme!r} needs >8-bit codes")
    return spx.codebook(levels, dtype=jnp.float32)


def quantize_kv(x, scheme: str = "uniform8", axis=-1):
    """Scheme-parameterized per-position quantization of K/V over a
    ``core/spx`` codebook. ``uniform8`` is the plain symmetric-int8
    baseline (255 uniform levels — NOT SPx); ``sp2_8`` / ``spx_8_x3`` are
    the paper's non-uniform level sets at the same 1-byte code width.
    x: (..., dh) -> (codes uint8, scale f32 (..., 1))."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axis,
                    keepdims=True)
    scale = jnp.maximum(scale, 1e-8)
    codes = spx.quantize_to_codes(x, spx.scheme_levels(scheme), scale)
    return codes, scale


def quantize_pages(pages, scheme: str = "uniform8"):
    """Page pools of values (..., n_pages, Hkv, page_size, dh) as the
    quantized pool {"codes" uint8 (..., page_size, dh), "scale" f32
    (..., n_pages, Hkv, lanes)}: the scale of a page's token t at lane t,
    the lanes past the page holding 1 as a fresh pool does."""
    codes, scale = quantize_kv(pages, scheme)
    pad = spx.kv_scale_lanes(pages.shape[-2]) - pages.shape[-2]
    scale = jnp.pad(scale[..., 0], [(0, 0)] * (scale.ndim - 2) + [(0, pad)],
                    constant_values=1.0)
    return {"codes": codes, "scale": scale}


def dequantize_kv(codes, scale, scheme: str = "uniform8",
                  dtype=jnp.float32):
    """codes (uint8) + per-position scale -> values: lut[codes] * scale."""
    return spx.dequantize_codes(codes, kv_lut(scheme), scale, dtype=dtype)


def _local_flash_decode(q, k_cache, v_cache, k_new, v_new, pos, *,
                        shard_size: int, axis: str | None,
                        kv_scheme: str = "uniform8"):
    """Per-shard decode body. Shapes (local view):
      q: (B, Hq, dh); caches: (B, Hkv, S_loc, dh) arrays, OR dicts
      {"codes" uint8 (B,Hkv,S_loc,dh), "scale" f32 (B,Hkv,S_loc,1)} for the
      quantized cache — codebook codes under ``kv_scheme`` (uniform8 =
      plain int8 baseline; sp2_8/spx_8_x3 = non-uniform SPx). Quantization
      roughly halves the decode step's HBM-bound term vs bf16 —
      EXPERIMENTS.md §Perf cell 1; k_new/v_new: (B, Hkv, dh);
      pos: (B,) int32 — per-sequence global write/attend position
      (continuous batching: slots decode at different depths).
    Returns (out (B, Hq, dh), k_cache, v_cache) updated.
    """
    quantized = isinstance(k_cache, dict)
    b, hq, dh = q.shape
    hkv = (k_cache["codes"] if quantized else k_cache).shape[1]
    s_loc = (k_cache["codes"] if quantized else k_cache).shape[2]
    rep = hq // hkv

    shard_idx = jax.lax.axis_index(axis) if axis else 0
    local_start = shard_idx * shard_size
    local_pos = pos - local_start                    # (B,)
    in_range = (local_pos >= 0) & (local_pos < s_loc)
    idx = jnp.clip(local_pos, 0, s_loc - 1)

    # per-row single-slot masked write: read old slot, select, write back
    def upd(cache, new):
        def row(c_b, n_b, ix, ok):
            old = jax.lax.dynamic_slice_in_dim(c_b, ix, 1, axis=1)
            val = jnp.where(ok, n_b[:, None, :].astype(c_b.dtype), old)
            return jax.lax.dynamic_update_slice_in_dim(c_b, val, ix, axis=1)
        return jax.vmap(row)(cache, new, idx, in_range)

    if quantized:
        lut = kv_lut(kv_scheme)
        kc_new, ks_new = quantize_kv(k_new, kv_scheme)  # (B,Hkv,dh),(B,Hkv,1)
        vc_new, vs_new = quantize_kv(v_new, kv_scheme)
        k_cache = {"codes": upd(k_cache["codes"], kc_new),
                   "scale": upd(k_cache["scale"], ks_new)}
        v_cache = {"codes": upd(v_cache["codes"], vc_new),
                   "scale": upd(v_cache["scale"], vs_new)}
        # scores: q . (lut[codes] * scale) == (q . lut[codes]) * scale —
        # the per-position scale folds out of the dh contraction, so the
        # LUT gather is the only dequant work (scheme-independent)
        kr = jnp.repeat(k_cache["codes"], rep, axis=1)     # uint8
        ksc = jnp.repeat(k_cache["scale"], rep, axis=1)    # (B,Hq,S,1)
        kd = jnp.take(lut, kr.astype(jnp.int32), axis=0)   # f32 levels
        s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32), kd)
        s = s * ksc[..., 0] * (dh ** -0.5)
    else:
        k_cache = upd(k_cache, k_new)
        v_cache = upd(v_cache, v_new)
        kr = jnp.repeat(k_cache, rep, axis=1)   # (B, Hq, S_loc, dh)
        s = jnp.einsum("bhd,bhkd->bhk", q.astype(jnp.float32),
                       kr.astype(jnp.float32)) * (dh ** -0.5)

    gpos = local_start + jnp.arange(s_loc)
    s = jnp.where(gpos[None, None, :] <= pos[:, None, None], s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)                 # (B, Hq, 1)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    if quantized:
        vr = jnp.repeat(v_cache["codes"], rep, axis=1)
        vsc = jnp.repeat(v_cache["scale"], rep, axis=1)
        vd = jnp.take(lut, vr.astype(jnp.int32), axis=0)
        # fold the per-position V scale into p before the level einsum
        pv = p * vsc[..., 0]
        o = jnp.einsum("bhk,bhkd->bhd", pv, vd)
    else:
        vr = jnp.repeat(v_cache, rep, axis=1)
        o = jnp.einsum("bhk,bhkd->bhd", p, vr.astype(jnp.float32))

    if axis is not None:
        # LSE merge across shards (tiny collectives: (B,Hq,1) and (B,Hq,dh))
        m_g = jax.lax.pmax(m, axis)
        corr = jnp.exp(m - m_g)
        l_g = jax.lax.psum(l * corr, axis)
        o = jax.lax.psum(o * corr, axis)
        out = o / jnp.maximum(l_g, 1e-30)
    else:
        out = o / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype), k_cache, v_cache


def decode_attention(q, k_cache, v_cache, k_new, v_new, pos, *, rt: Runtime):
    """One-token attention against the cache, updating it.

    q: (B, Hq, dh); caches (B, Hkv, S, dh) [seq-sharded over rt.decode_seq_axis
    when a mesh is active]; k_new/v_new: (B, Hkv, dh); pos: () or (B,) int32
    (per-sequence positions for continuous batching).
    Returns (out, k_cache, v_cache).
    """
    quantized = isinstance(k_cache, dict)
    scheme = rt.kv_scheme
    s_total = (k_cache["codes"] if quantized else k_cache).shape[2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (q.shape[0],))
    if rt.mesh is None or rt.decode_seq_axis is None:
        return _local_flash_decode(q, k_cache, v_cache, k_new, v_new, pos,
                                   shard_size=s_total, axis=None,
                                   kv_scheme=scheme)

    axis = rt.decode_seq_axis
    n_shards = rt.mesh.shape[axis]
    if s_total % n_shards or (rt.data_axes and
                              q.shape[0] % _n_axes(rt.mesh, rt.data_axes)):
        # non-divisible (tiny test shapes): local path, replicated
        return _local_flash_decode(q, k_cache, v_cache, k_new, v_new, pos,
                                   shard_size=s_total, axis=None,
                                   kv_scheme=scheme)
    shard_size = s_total // n_shards
    dp = rt.data_axes if rt.data_axes else None
    arr_spec = P(dp, None, axis, None)
    cache_spec = ({"codes": arr_spec, "scale": arr_spec} if quantized
                  else arr_spec)
    rep_spec = P(dp, None, None)

    fn = jax.shard_map(
        functools.partial(_local_flash_decode, shard_size=shard_size,
                          axis=axis, kv_scheme=scheme),
        mesh=rt.mesh,
        in_specs=(rep_spec, cache_spec, cache_spec, rep_spec, rep_spec,
                  P(dp)),
        out_specs=(rep_spec, cache_spec, cache_spec),
        check_vma=False,
    )
    return fn(q, k_cache, v_cache, k_new, v_new, pos)


def _n_axes(mesh, axes) -> int:
    n = 1
    for a in axes:
        n *= dict(mesh.shape)[a]
    return n


# ---------------------------------------------------------------------------
# Paged KV cache: chunked prefill + paged decode (serving — docs/SERVING.md)
# ---------------------------------------------------------------------------

def paged_kv_write(k_pages, v_pages, k_new, v_new, layer, block_table,
                   ctx_len, n_valid, *, kv_scheme: str = "uniform8",
                   impl: str = "auto", mesh=None, head_axis=None):
    """Write a chunk of new K/V rows into the stacked page pools, in
    place (``ops.paged_kv_write``).

    k_pages/v_pages: every layer's pools, (P, n_pages, Hkv, page_size,
    dh) arrays, OR dicts {"codes" uint8 (P, n_pages, Hkv, page_size, dh),
    "scale" f32 (P, n_pages, Hkv, lanes)} for the quantized pool (codes
    under ``kv_scheme``, the scale of a page's token t at lane t);
    k_new/v_new: (B, C, Hkv, dh); layer: int32 scalar, the pools' layer
    written; block_table: (B, max_pages) int32; row i of slot b goes to
    position ``ctx_len[b] + i`` if ``i < n_valid[b]`` — the other rows
    (chunk padding, inactive slots) are not written. ``mesh`` and
    ``head_axis`` as in ``ops.paged_kv_write``.
    """
    quantized = isinstance(k_pages, dict)
    if quantized:
        kc, ks = quantize_kv(k_new, kv_scheme)    # (B,C,Hkv,dh), (B,C,Hkv,1)
        vc, vs = quantize_kv(v_new, kv_scheme)
        pools = [k_pages["codes"], k_pages["scale"], v_pages["codes"],
                 v_pages["scale"]]
        rows = [kc, ks[..., 0], vc, vs[..., 0]]
    else:
        pools, rows = [k_pages, v_pages], [k_new, v_new]
    out = ops.paged_kv_write(pools, rows, layer, block_table, ctx_len,
                             n_valid, impl=impl, mesh=mesh,
                             head_axis=head_axis)
    if quantized:
        return ({"codes": out[0], "scale": out[1]},
                {"codes": out[2], "scale": out[3]})
    return out[0], out[1]


def _gather_pages(pages, layer, block_table, kv_scheme: str):
    """Gather one sequence's pages of one layer into a contiguous
    (B, Hkv, S, dh) view; the layer and page indices form one gather, so
    no layer of the stacked pool is sliced out first. Dict (quantized)
    pools are dequantized after the gather, so the f32 values are
    materialized *context-sized* (S = max_pages x page_size) per chunk
    call — only the HBM-resident pool stays 1 byte/element. That's the
    prefill path's trade (compute-bound, gather amortized); the decode
    hot path never does this, it streams codes through the fused-dequant
    kernel instead."""
    bt = block_table
    b = bt.shape[0]
    if isinstance(pages, dict):
        hkv, ps, dh = pages["codes"].shape[2:]
        codes = jnp.moveaxis(pages["codes"][layer, bt], 2, 1) \
            .reshape(b, hkv, bt.shape[1] * ps, dh)
        scale = kref.page_scales(pages["scale"][layer, bt], ps)
        return dequantize_kv(codes, scale, kv_scheme, dtype=jnp.float32)
    hkv, ps, dh = pages.shape[2:]
    return jnp.moveaxis(pages[layer, bt], 2, 1).reshape(
        b, hkv, bt.shape[1] * ps, dh)


def _paged_chunk_attention(q, k_pages, v_pages, layer, block_table,
                           positions, attend_len,
                           kv_scheme: str = "uniform8"):
    """Attention of a C-token chunk against the full paged context
    (including the chunk itself, already written to the pages).

    q: (B, Hq, C, dh); k_pages/v_pages: the stacked pools, read at
    ``layer``; positions: (B, C) absolute query positions; attend_len:
    (B,) total attendable tokens. Gathers this sequence's pages into a
    contiguous view — prefill is compute-bound, so the gather's bytes are
    amortized; the decode tick goes through the ragged decode kernel
    instead. Returns (B, Hq, C, dh).
    """
    b, hq, c, dh = q.shape
    quantized = isinstance(k_pages, dict)
    hkv, ps = (k_pages["codes"] if quantized else k_pages).shape[2:4]
    s_max = block_table.shape[1] * ps
    rep = hq // hkv
    k = _gather_pages(k_pages, layer, block_table, kv_scheme)
    v = _gather_pages(v_pages, layer, block_table, kv_scheme)
    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (dh ** -0.5)
    kv_pos = jnp.arange(s_max)
    mask = ((kv_pos[None, None, :] <= positions[:, :, None])
            & (kv_pos[None, None, :] < attend_len[:, None, None]))
    s = jnp.where(mask[:, None], s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return (o / jnp.maximum(l, 1e-30)).astype(q.dtype)


def attn_paged_step(p: dict, x: jax.Array, ctx_len: jax.Array,
                    block_table: jax.Array, cache: dict, layer, *,
                    n_heads: int, n_kv_heads: int, head_dim: int,
                    n_valid: jax.Array, rope_theta: float = 10000.0,
                    mrope_sections=None, rt: Runtime, fused: bool = False):
    """Attention sublayer over the paged KV cache, for a prefill chunk
    and for the decode window alike.

    x: (B, C, D) — the next C tokens of each sequence; ctx_len: (B,) int32
    tokens already in the pages; n_valid: (B,) int32 valid tokens in this
    chunk (< C for ragged tails / inactive rows — invalid tokens are
    neither written nor trusted); cache: {"kp", "vp"} physical pools of
    every layer, stacked on axis 0 — arrays, or {"codes", "scale"} dicts
    for the quantized pool (``rt.kv_scheme`` picks the level set); layer:
    int32 scalar, this sublayer's index into them — the new rows are
    written and read there in place. ``fused`` (the serving engine's
    decode tick: the pending token, plus the speculative verify window)
    attends through the ragged decode megakernel
    (``ops.paged_decode_ragged``) — one launch for the whole ragged
    window, n_valid as the per-slot ``q_len``, dense or quantized pools
    alike. Otherwise the C-token prefill chunk, of any C, attends through
    the page gather (``_paged_chunk_attention``).
    ``rope_theta`` None: no positional encoding (NoPE). Returns
    (y (B, C, D), new_cache).
    """
    b, c, _ = x.shape
    quantized = isinstance(cache["kp"], dict)
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, rt)
    positions = ctx_len[:, None] + jnp.arange(c, dtype=jnp.int32)   # (B, C)
    if mrope_sections is not None:
        # text-stream M-RoPE paged positions: the three rotary streams
        # share the token index (equivalent to plain RoPE for text-only
        # decode — exactly what the dense path broadcasts). RoPE happens
        # before the cache write, so the fused/quantized paths need no
        # position plumbing of their own.
        positions3 = jnp.broadcast_to(positions[:, None, :], (b, 3, c))
        q, k = _apply_positional(q, k, positions3, rope_theta,
                                 mrope_sections)
    else:
        q, k = _apply_positional(q, k, positions, rope_theta, None)
    with jax.named_scope("kv_write"):
        kp, vp = paged_kv_write(cache["kp"], cache["vp"], k, v, layer,
                                block_table, ctx_len, n_valid,
                                kv_scheme=rt.kv_scheme, impl=rt.impl,
                                mesh=rt.mesh, head_axis=rt.model_axis)
    attend_len = ctx_len + n_valid
    if fused:
        # one megakernel launch for the whole (slot, attend_len) ragged
        # window — window row i of slot b attends cache positions
        # <= ctx_len[b] + i, rows past n_valid[b] come back zero (unused)
        out = ops.paged_decode_ragged(
            q, kp, vp, block_table, ctx_len, n_valid, layer=layer,
            kv_scheme=rt.kv_scheme if quantized else None, impl=rt.impl,
            mesh=rt.mesh, head_axis=rt.model_axis)
        o = out.reshape(b, c, n_heads * head_dim)
    else:
        qh = jnp.swapaxes(q, 1, 2)                       # (B, Hq, C, dh)
        o = _paged_chunk_attention(qh, kp, vp, layer, block_table,
                                   positions, attend_len,
                                   kv_scheme=rt.kv_scheme)
        o = jnp.swapaxes(o, 1, 2).reshape(b, c, n_heads * head_dim)
    y = dense_apply(p["wo"], o, rt)
    return y, dict(cache, kp=kp, vp=vp)


def attn_decode_step(p: dict, x: jax.Array, pos: jax.Array, kv_cache: tuple, *,
                     n_heads: int, n_kv_heads: int, head_dim: int,
                     rope_theta: float = 10000.0, mrope_sections=None,
                     rt: Runtime, cross_kv: tuple | None = None):
    """One-token attention sublayer. x: (B, 1, D); kv_cache: (k, v) each
    (B, Hkv, S, dh). Returns (y (B,1,D), new_cache)."""
    b = x.shape[0]
    q, k, v = _project_qkv(p, x, n_heads, n_kv_heads, head_dim, rt)
    pos_b = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    if mrope_sections is not None:
        positions3 = jnp.broadcast_to(pos_b[:, None, None], (b, 3, 1))
        q, k = _apply_positional(q, k, positions3, rope_theta, mrope_sections)
    else:
        q, k = _apply_positional(q, k, pos_b[:, None], rope_theta,
                                 mrope_sections)

    if cross_kv is not None:
        # cross-attention: static KV (encoder output projections), no cache
        kh = jnp.swapaxes(cross_kv[0], 1, 2)
        vh = jnp.swapaxes(cross_kv[1], 1, 2)
        qh = jnp.swapaxes(q, 1, 2)
        o = attention_core(qh, kh, vh, causal=False, rt=rt)
        y = jnp.swapaxes(o, 1, 2).reshape(b, 1, n_heads * head_dim)
        return dense_apply(p["wo"], y, rt), kv_cache

    k_cache, v_cache = kv_cache
    out, k_cache, v_cache = decode_attention(
        q[:, 0].reshape(b, n_heads, head_dim),
        k_cache, v_cache,
        k[:, 0].reshape(b, n_kv_heads, head_dim),
        v[:, 0].reshape(b, n_kv_heads, head_dim),
        pos, rt=rt)
    y = dense_apply(p["wo"], out.reshape(b, 1, n_heads * head_dim), rt)
    return y, (k_cache, v_cache)
