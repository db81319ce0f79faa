"""Analytical block planner: per-shape (bm, bn, bk) / (bq, bkv) / KV-page
selection.

The paper's §3.1 soundness condition — loading must stay ahead of compute —
is evaluated analytically by ``core/pipeline.plan_matmul_blocks``; this
module turns it into the *execution plan* the kernels actually run with,
instead of the old one-size-fits-all ``DEFAULT_BM/BN/BK`` constants:

  * candidate blocks are filtered to exact divisors of (N, K) (and (Sq,
    Skv) for attention) so the Pallas grid tiles the problem with no
    remainder — M alone is padded by the ops wrapper;
  * the surviving candidate maximizing (pipelined, margin, -vmem) under the
    VMEM budget wins; plans are lru-cached per shape so planning is free
    after the first trace;
  * ``None`` means no legal blocking exists (ragged dims) and the caller
    falls back to the jnp reference path — exactly the old behavior, now in
    one place.

The same VMEM-budget model sizes the serving KV pages (``plan_kv_pages``):
a page is the unit the paged-attention decode kernel streams HBM→VMEM per
grid step, so it is chosen like any other tile — double-buffered K+V page
pair under ``VMEM_BUDGET_FRACTION``, floored at the dtype's sublane tile.

All sizes in this module are **element counts** (tokens, rows, columns)
except fields and helpers explicitly suffixed ``_bytes``; activation /
weight widths enter as ``act_bytes`` / ``weight_bits``.

Caching: every ``plan_*`` entry point memoizes per concrete shape tuple via
``functools.lru_cache`` — the first call per shape does the search, later
calls (including every jit retrace) are dict hits. ``clear_plan_cache()``
drops all cached plans and measured-autotune winners (tests use it when
flipping env overrides).

Environment overrides (read at call time, not import time):
  REPRO_BLOCKS_MATMUL="bm,bn,bk"  pin matmul blocks (divisibility checked)
  REPRO_BLOCKS_ATTN="bq,bkv"      pin attention blocks
  REPRO_PAGE_SIZE=N               pin the KV page size (tokens per page)
  REPRO_AUTOTUNE=1                measured autotuning: ops wrappers time the
                                  top analytical candidates on the real
                                  kernel and cache the winner per shape
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Callable, Optional

from repro.core.pipeline import HwSpec, current_hw, plan_matmul_blocks
from repro.core.spx import int4_group

__all__ = [
    "MatmulBlocks", "AttentionBlocks", "KVPagePlan", "FusedDecodePlan",
    "StateReservation",
    "ShardBudget",
    "plan_matmul", "plan_attention", "plan_kv_pages", "plan_seq_pages",
    "plan_resume_pages", "plan_seq_state", "plan_shard_budget",
    "plan_fused_decode", "fused_decode_key", "matmul_candidates",
    "autotune_enabled", "measured_best", "measured_plan",
    "clear_plan_cache", "DEFAULT_BM", "VMEM_BUDGET_FRACTION",
]

# bm candidate ceiling for tiny-M problems (M is padded to the chosen bm,
# so candidates above this only waste padding). This is the only default
# tile constant left in the tree — kernels take explicit blocks now.
DEFAULT_BM = 256

#: fraction of per-core VMEM a plan may claim (double buffers + scratch
#: accounting lives in core/pipeline._block_cost)
VMEM_BUDGET_FRACTION = 0.9

_BLOCK_CANDIDATES = (2048, 1024, 512, 384, 256, 128, 64, 32, 16, 8)


@dataclasses.dataclass(frozen=True)
class MatmulBlocks:
    bm: int
    bn: int
    bk: int
    pipelined: bool          # t_load <= t_compute (paper's §3.1 condition)
    margin: float            # compute/load ratio; >1 => DMA fully hidden
    vmem_bytes: int


@dataclasses.dataclass(frozen=True)
class AttentionBlocks:
    bq: int
    bkv: int
    pipelined: bool
    margin: float
    vmem_bytes: int


@dataclasses.dataclass(frozen=True)
class KVPagePlan:
    """Geometry for the paged KV cache (serving decode path).

    page_size    tokens per page — the unit the paged-attention kernel
                 streams per grid step AND the allocator's granularity
    pipelined    §3.1 condition for the decode kernel's page loop
    margin       compute/load ratio for one (K, V) page pair
    vmem_bytes   kernel working set: double-buffered K+V page pair +
                 q/acc/stats scratch, in bytes
    """
    page_size: int
    pipelined: bool
    margin: float
    vmem_bytes: int


def _divisors(dim: int) -> tuple[int, ...]:
    out = tuple(c for c in _BLOCK_CANDIDATES
                if c <= dim and dim % c == 0)
    # the §3.1 margin is block-size-neutral along K (load and compute both
    # scale linearly), so an unfiltered search ties and the min-VMEM
    # tie-break degenerates to 8-wide tiles. Floor candidates at the MXU
    # tile (128) when the dim admits one — sub-MXU tiles waste the systolic
    # array no matter what the byte model says.
    if out:
        floor = min(128, max(out))
        out = tuple(c for c in out if c >= floor)
    return out


def _bn_legal(n: int, bn: int, packed: bool) -> bool:
    """bn tiles n, and an int4-packed tile holds whole lane groups
    (``core/spx.pack_int4``) so the kernel can unpack it on its own."""
    return n % bn == 0 and not (packed and bn % (2 * int4_group(n)))


def matmul_candidates(m: int, k: int, n: int, *,
                      packed: bool = False) -> tuple:
    """(bm, bn, bk) candidate tuples under the divisibility rules the
    Pallas wrapper needs: bn | n, bk | k (bn a multiple of two int4 lane
    groups when packed — the whole dim when it has one group); bm is
    free (M is padded).

    Units: ``m``/``k``/``n`` are matrix dims in elements; returned
    candidates are tile dims in elements. Pure function, no caching —
    callers (``_plan_matmul_cached``, the autotuner) cache downstream.
    """
    bm_c = tuple(c for c in _BLOCK_CANDIDATES if c <= max(m, DEFAULT_BM))
    bn_c = tuple(c for c in _divisors(n) if _bn_legal(n, c, packed))
    if packed and not bn_c and n <= max(_BLOCK_CANDIDATES):
        bn_c = (n,)
    bk_c = _divisors(k)
    return bm_c, bn_c, bk_c


def _env_override(var: str, n_fields: int) -> Optional[tuple[int, ...]]:
    raw = os.environ.get(var)
    if not raw:
        return None
    parts = tuple(int(p) for p in raw.replace(" ", "").split(","))
    if len(parts) != n_fields:
        raise ValueError(f"{var}={raw!r}: expected {n_fields} ints")
    return parts


@functools.lru_cache(maxsize=4096)
def _plan_matmul_cached(m: int, k: int, n: int, weight_bits: int,
                        act_bytes: int, packed: bool,
                        hw: HwSpec) -> Optional[MatmulBlocks]:
    bm_c, bn_c, bk_c = matmul_candidates(m, k, n, packed=packed)
    if not bn_c or not bk_c:
        return None                       # ragged dims: ref fallback
    plan = plan_matmul_blocks(m, n, k, weight_bits=weight_bits,
                              act_bytes=act_bytes, hw=hw,
                              candidates_m=bm_c, candidates_n=bn_c,
                              candidates_k=bk_c,
                              vmem_fraction=VMEM_BUDGET_FRACTION)
    # the tiny-problem fallback inside plan_matmul_blocks ignores the
    # candidate filter; re-check divisibility before trusting it
    if not _bn_legal(n, plan.bn, packed) or k % plan.bk:
        return None
    return MatmulBlocks(plan.bm, plan.bn, plan.bk, plan.pipelined,
                        plan.margin, plan.vmem_bytes)


def plan_matmul(m: int, k: int, n: int, *, weight_bits: int = 16,
                act_bytes: int = 2, packed: bool = False,
                hw: HwSpec | None = None) -> Optional[MatmulBlocks]:
    """Blocks for x:(M,K) @ W:(K,N) with b-bit SPx weight codes, or None if
    no legal blocking exists (caller falls back to the ref path).

    Units: ``m``/``k``/``n`` in elements; ``weight_bits`` per weight code
    (4 or 8 for SPx, 16 for dense bf16); ``act_bytes`` per activation
    element; ``MatmulBlocks.vmem_bytes`` is the kernel working set in
    bytes. Cached per (m, k, n, weight_bits, act_bytes, packed, hw);
    ``REPRO_BLOCKS_MATMUL="bm,bn,bk"`` pins the blocks (divisibility still
    checked; returns None — i.e. ref fallback — when the pin is illegal
    for this shape) and bypasses the cache.
    """
    pinned = _env_override("REPRO_BLOCKS_MATMUL", 3)
    if pinned is not None:
        bm, bn, bk = pinned
        if not _bn_legal(n, bn, packed) or k % bk:
            return None
        return MatmulBlocks(bm, bn, bk, False, 0.0, 0)
    return _plan_matmul_cached(m, k, n, weight_bits, act_bytes, packed,
                               hw or current_hw())


@functools.lru_cache(maxsize=4096)
def _plan_attention_cached(sq: int, skv: int, dh: int, act_bytes: int,
                           hw: HwSpec) -> Optional[AttentionBlocks]:
    best = None
    for bq in _divisors(sq):
        for bkv in _divisors(skv):
            # per inner grid step: stream the next (K, V) tile pair while
            # the MXU runs QK^T + PV on the current one (q stays resident
            # across the KV loop)
            t_load = 2 * bkv * dh * act_bytes / hw.hbm_bw
            t_compute = 4.0 * bq * bkv * dh / hw.peak_bf16_flops
            vmem = (2 * (2 * bq * dh + 2 * bkv * dh) * act_bytes
                    + bq * dh * 4 + 2 * bq * 4       # acc + (m, l) scratch
                    + 2 * bq * bkv * 4)              # f32 scores + probs
            if vmem > hw.vmem_bytes * VMEM_BUDGET_FRACTION:
                continue
            margin = t_compute / max(t_load, 1e-30)
            plan = AttentionBlocks(bq, bkv, t_load <= t_compute, margin,
                                   int(vmem))
            key = (plan.pipelined, plan.margin, -plan.vmem_bytes)
            if best is None or key > (best.pipelined, best.margin,
                                      -best.vmem_bytes):
                best = plan
    return best


def plan_attention(sq: int, skv: int, dh: int, *, act_bytes: int = 2,
                   hw: HwSpec | None = None) -> Optional[AttentionBlocks]:
    """(bq, bkv) for flash attention over (Sq, Skv, dh), or None when the
    sequence dims admit no candidate blocking (ref fallback).

    Units: ``sq``/``skv``/``dh`` are element counts; ``act_bytes`` is the
    per-element width of Q/K/V. Cached per (sq, skv, dh, act_bytes, hw);
    the ``REPRO_BLOCKS_ATTN`` override bypasses the cache entirely.
    """
    pinned = _env_override("REPRO_BLOCKS_ATTN", 2)
    if pinned is not None:
        bq, bkv = pinned
        if sq % bq or skv % bkv:
            return None
        return AttentionBlocks(bq, bkv, False, 0.0, 0)
    return _plan_attention_cached(sq, skv, dh, act_bytes,
                                  hw or current_hw())


# ---------------------------------------------------------------------------
# KV page sizing (serving)
# ---------------------------------------------------------------------------

#: tokens-per-page candidates, ascending — ties in the §3.1 score resolve
#: to the SMALLEST page (least fragmentation waste per sequence tail)
_PAGE_CANDIDATES = (8, 16, 32, 64, 128, 256)


def _sublane_floor(act_bytes: int) -> int:
    """Minimum second-to-last tile dim for the cache dtype (TPU tiling:
    f32 -> 8, bf16 -> 16, int8 -> 32). Pages sit on the sublane axis of the
    kernel's (page_size, dh) K/V blocks, so smaller pages than this would
    be padded to a full tile anyway."""
    return max(8, 32 // max(act_bytes, 1))


@functools.lru_cache(maxsize=256)
def _plan_kv_pages_cached(n_kv_heads: int, dh: int, rep: int,
                          act_bytes: int, tok_bytes: int,
                          floor_bytes: int, hw: HwSpec) -> KVPagePlan:
    del n_kv_heads  # the kernel grids over KV heads; per-step cost is 1 head
    best = None
    best_key = None
    for ps in _PAGE_CANDIDATES:
        if ps < _sublane_floor(floor_bytes):
            continue
        # per grid step: stream the next (K, V) page pair for one KV head
        # while the MXU runs QK^T + PV (rep query heads) on the current one.
        # ``tok_bytes`` is one token's K *or* V bytes for one head — dh x
        # act_bytes dense, dh x 1 + 4 for the codes+scale quantized pool
        # (the fused-dequant kernel streams codes, not values).
        t_load = 2 * ps * tok_bytes / hw.hbm_bw
        t_compute = 4.0 * rep * ps * dh / hw.peak_bf16_flops
        vmem = (2 * 2 * ps * tok_bytes           # double-buffered K+V pages
                + rep * dh * act_bytes           # resident q
                + rep * dh * 4 + 2 * rep * 4)    # f32 acc + (m, l) scratch
        if vmem > hw.vmem_bytes * VMEM_BUDGET_FRACTION:
            continue
        margin = t_compute / max(t_load, 1e-30)
        # NOTE: the margin is page-size-neutral (load and compute both scale
        # linearly in page_size), so the score usually ties and the
        # ascending iteration keeps the smallest legal page — exactly what
        # fragmentation wants. The score still matters when VMEM excludes
        # candidates or a future HwSpec breaks the linearity.
        key = (t_load <= t_compute, margin)
        if best is None or key > best_key:
            best = KVPagePlan(ps, t_load <= t_compute, margin, int(vmem))
            best_key = key
    if best is None:                    # dh so large nothing fits: min tile
        ps = _sublane_floor(floor_bytes)
        best = KVPagePlan(ps, False, 0.0, 0)
    return best


def plan_kv_pages(n_kv_heads: int, dh: int, *, rep: int = 1,
                  act_bytes: int = 2, kv_scheme: str | None = None,
                  hw: HwSpec | None = None) -> KVPagePlan:
    """Tokens-per-page for the paged KV cache.

    Units: ``n_kv_heads``/``dh`` are element counts (the cache page is
    ``page_size x dh`` elements per KV head); ``rep = Hq // Hkv`` is the
    GQA expansion (query heads served per KV page); ``act_bytes`` is the
    cache element width in bytes. ``kv_scheme`` (a core/spx scheme name)
    switches the byte model to the quantized codes+scale page layout —
    ``dh x 1 + 4`` bytes per token side instead of ``dh x act_bytes`` —
    and floors the page at the uint8 sublane tile (32).

    Cached per argument tuple (lru); ``REPRO_PAGE_SIZE=N`` pins the page
    size, bypassing both the model and the cache. Always returns a plan —
    there is no ref-fallback ``None`` here because any page size is legal
    for the allocator; an unpipelined plan just means the decode kernel is
    HBM-bound (which single-token decode always is: margin < 1 whenever
    ``2 * rep * peak_flops_byte < 1``).
    """
    pinned = _env_override("REPRO_PAGE_SIZE", 1)
    if pinned is not None:
        return KVPagePlan(pinned[0], False, 0.0, 0)
    if kv_scheme is not None:
        from repro.core.spx import KV_CODE_BYTES, kv_token_side_bytes
        tok_bytes, floor_bytes = kv_token_side_bytes(dh), KV_CODE_BYTES
    else:
        tok_bytes, floor_bytes = dh * act_bytes, act_bytes
    return _plan_kv_pages_cached(n_kv_heads, dh, rep, act_bytes, tok_bytes,
                                 floor_bytes, hw or current_hw())


def plan_seq_pages(n_tokens: int, page_size: int, *,
                   shared_tokens: int = 0) -> int:
    """Fresh pages a sequence must reserve at admission.

    The worst-case reservation is ``ceil(n_tokens / page_size)`` pages;
    a matched shared prefix of ``shared_tokens`` tokens maps
    ``shared_tokens // page_size`` of them from the pool's prefix index
    instead (refcount bump, no new page, no prefill work). The floor
    deliberately bills a *partially* reused last page as fresh: that is
    the copy-on-write case — the engine copies the matched page into a
    private one before the sequence writes into it — so the COW
    destination is correctly part of the fresh reservation.

    Units are tokens and pages, which makes the count layout-neutral: a
    page holds ``page_size`` tokens whether its device arrays store dense
    ``act_bytes`` elements or the quantized codes+scale pair
    (``plan_kv_pages`` sizes both layouts to the same token geometry), so
    one reservation model covers plain and kv_quant pools.
    """
    if page_size < 1 or n_tokens < 0 or not 0 <= shared_tokens <= n_tokens:
        raise ValueError((n_tokens, page_size, shared_tokens))
    return -(-n_tokens // page_size) - shared_tokens // page_size


def plan_resume_pages(n_written: int, n_total: int,
                      page_size: int) -> tuple[int, int]:
    """Page plan for resuming a preempted sequence:
    ``(pages_total, pages_restored)``.

    ``pages_total`` is the full worst-case reservation the sequence needs
    back on device (``plan_seq_pages`` of its prompt + max_new budget —
    resumption re-reserves exactly what admission did, so a resumed
    request can never OOM mid-decode any more than a fresh one can).
    ``pages_restored`` is the leading slice of that reservation which
    must be refilled from the host snapshot: the pages covering the
    ``n_written`` tokens that were actually in the cache at preemption
    (the write cursor) — everything past the cursor is unwritten (or a
    rejected speculative tail that was never attended) and restores as
    blank pages for free. No prefix sharing: the restored bytes are
    private by construction.
    """
    if not 0 <= n_written <= n_total:
        raise ValueError((n_written, n_total, page_size))
    return (plan_seq_pages(n_total, page_size),
            plan_seq_pages(n_written, page_size))


@dataclasses.dataclass(frozen=True)
class StateReservation:
    """Per-sequence admission footprint across the StateCache regions:
    ``pages`` of token-paged KV (fresh pages after prefix discount),
    ``slabs`` of recurrent SSM state (0 or 1 — one slab covers every SSM
    slot x period), ``cross`` read-only encoder-output KV entries (0 or 1;
    a prefix-index hit on the frames key costs 0 fresh entries, but the
    reservation bills the miss case — admission is worst-case, like
    pages)."""
    pages: int
    slabs: int
    cross: int


def plan_seq_state(n_tokens: int, page_size: int, *,
                   shared_tokens: int = 0, needs_pages: bool = True,
                   needs_slab: bool = False,
                   needs_cross: bool = False) -> StateReservation:
    """Admission reservation for one sequence under the unified
    state-cache: the ``plan_seq_pages`` token->page model for the
    attention slots (0 pages when the pattern has none — pure-SSM models
    run pageless), plus one slab when any SSM slot needs recurrent state,
    plus one cross entry when the model decodes against encoder output.
    The page/slab/cross split is what ``StateCache.allocate`` checks
    all-or-nothing at admission."""
    pages = plan_seq_pages(n_tokens, page_size,
                           shared_tokens=shared_tokens) if needs_pages \
        else 0
    return StateReservation(pages=pages, slabs=int(bool(needs_slab)),
                            cross=int(bool(needs_cross)))


# ---------------------------------------------------------------------------
# Per-shard budgets (tensor-parallel serving)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardBudget:
    """Where one model shard's serving memory actually goes when the paged
    pools are head-sharded over a ``model`` axis of size ``shards``.

    kv_sharded            the pool's KV-head axis divides evenly, so each
                          shard holds ``kv_heads_per_shard`` of it; when
                          False the pool replicates (each shard holds all
                          heads) and the per-shard numbers equal global
    kv_heads_per_shard    KV heads resident per shard
    page_bytes            ONE page's bytes on one shard (all layers)
    pool_bytes            the whole page pool's bytes on one shard
    slab_bytes            recurrent-slab bytes on one shard — slabs
                          replicate (sequence-private state, no head axis)
    vmem_bytes            the decode kernel's per-step working set; the
                          kernel grids over (slot, kv_head, page) so the
                          per-step set is one head's page pair regardless
                          of how many heads the shard holds — sharding
                          changes grid length, not VMEM pressure
    """
    shards: int
    kv_sharded: bool
    kv_heads_per_shard: int
    page_bytes: int
    pool_bytes: int
    slab_bytes: int
    vmem_bytes: int


@functools.lru_cache(maxsize=256)
def _plan_shard_budget_cached(n_kv_heads: int, dh: int, shards: int,
                              page_size: int, n_pages: int, n_layers: int,
                              slab_bytes: int, page_side_bytes: int,
                              vmem_bytes: int) -> ShardBudget:
    kv_sharded = shards > 1 and n_kv_heads % shards == 0
    heads = n_kv_heads // shards if kv_sharded else n_kv_heads
    # K + V sides, all paged layers, the shard's resident heads
    page_bytes = 2 * page_side_bytes * heads * n_layers
    return ShardBudget(shards=shards, kv_sharded=kv_sharded,
                       kv_heads_per_shard=heads, page_bytes=page_bytes,
                       pool_bytes=page_bytes * n_pages,
                       slab_bytes=slab_bytes, vmem_bytes=vmem_bytes)


def plan_shard_budget(n_kv_heads: int, dh: int, *, shards: int = 1,
                      page_size: int, n_pages: int, n_layers: int = 1,
                      slab_bytes: int = 0, act_bytes: int = 2,
                      kv_scheme: str | None = None,
                      hw: HwSpec | None = None) -> ShardBudget:
    """Per-shard page/slab/VMEM budget for a tensor-parallel paged engine.

    The page pool is ``(layers, n_pages, Hkv, page_size, dh)`` per K/V
    side; sharding splits the ``Hkv`` axis over ``shards`` model-parallel
    devices when it divides (else the pool replicates — same
    divisibility-or-replicate rule ``ShardingPolicy`` applies to params).
    ``slab_bytes`` (recurrent state, per-sequence) never shards.
    ``kv_scheme`` switches the per-token byte model to the quantized
    codes+scale layout, same as ``plan_kv_pages``. The VMEM figure is the
    decode kernel's per-step working set and is deliberately
    shard-neutral: the kernel's grid covers the shard's heads
    sequentially, so fewer resident heads shorten the grid without
    changing the per-step footprint.
    """
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    if kv_scheme is not None:
        # codes + the page's lane-padded scale row
        from repro.core.spx import kv_page_side_bytes
        page_side = kv_page_side_bytes(dh, page_size)
    else:
        page_side = page_size * dh * act_bytes
    plan = plan_kv_pages(n_kv_heads, dh, act_bytes=act_bytes,
                         kv_scheme=kv_scheme, hw=hw)
    return _plan_shard_budget_cached(n_kv_heads, dh, shards, page_size,
                                     n_pages, n_layers, slab_bytes,
                                     page_side, plan.vmem_bytes)


# ---------------------------------------------------------------------------
# Fused ragged-decode megakernel sizing (serving)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FusedDecodePlan:
    """VMEM model for one grid step of the ragged decode megakernel.

    The kernel grids over (slot, kv_head, page); per step it holds the
    whole (rep * w, dh) query window + f32 accumulator/stats resident,
    double-buffers one K+V page pair (codes + per-token scale for a
    quantized pool), and — quantized pools only — keeps the <=256-entry
    codebook LUT resident for the entire launch (``kernels/decode.py``:
    VMEM rows for the lane gather, SMEM for the select chain) and decodes
    each page in VMEM (int32 codes, then f32 levels, for K and V).

    rows         rep * w query rows per grid step (w = spec K+1, or 1)
    lut_bytes    resident codebook bytes (0 for a dense pool)
    pipelined    §3.1 condition for the page loop at this window size
    margin       compute/load ratio for one (K, V) page pair
    vmem_bytes   total per-step working set in bytes
    """
    rows: int
    lut_bytes: int
    pipelined: bool
    margin: float
    vmem_bytes: int


@functools.lru_cache(maxsize=256)
def _plan_fused_decode_cached(dh: int, rep: int, w: int, page_size: int,
                              act_bytes: int, tok_bytes: int,
                              lut_bytes: int, hw: HwSpec) -> FusedDecodePlan:
    rows = rep * w
    # per grid step: stream the next (K, V) page pair while the MXU runs
    # QK^T + PV for all ``rows`` window rows on the current one
    t_load = 2 * page_size * tok_bytes / hw.hbm_bw
    t_compute = 4.0 * rows * page_size * dh / hw.peak_bf16_flops
    decode = 2 * page_size * dh * (4 + 4) if lut_bytes else 0
    vmem = (2 * 2 * page_size * tok_bytes     # double-buffered K+V pages
            + 2 * 2 * rows * dh * act_bytes   # q window in, output out
            + rows * dh * 4 + 2 * rows * 4    # f32 acc + (m, l) scratch
            + decode)                         # in-VMEM K/V page decode
    margin = t_compute / max(t_load, 1e-30)
    return FusedDecodePlan(rows, lut_bytes, t_load <= t_compute, margin,
                           int(vmem))


def plan_fused_decode(dh: int, *, rep: int = 1, w: int = 1,
                      page_size: int = 8, act_bytes: int = 2,
                      kv_scheme: str | None = None,
                      hw: HwSpec | None = None) -> FusedDecodePlan:
    """Working-set model for the ragged decode megakernel.

    Units: ``dh`` and ``page_size`` are element/token counts; ``rep = Hq //
    Hkv``; ``w`` is the static decode window (spec K+1, or 1 for plain
    decode); ``act_bytes`` the query/cache element width. ``kv_scheme``
    switches the streamed-page byte model to the quantized codes+scale
    layout, counts the scheme's codebook LUT (resident for the whole
    launch, fetched once) and charges the per-page decode to VMEM.

    Always returns a plan — page geometry was already fixed by
    ``plan_kv_pages`` at pool allocation, so there is no candidate search
    here, just the §3.1 accounting for the window the engine runs. The w
    factor is why the megakernel pays off: compute grows with ``rep * w``
    per streamed page while load stays constant, so the verify window
    pushes ``margin`` toward pipelined where single-row decode is
    hopelessly HBM-bound.
    """
    if kv_scheme is not None:
        from repro.core.spx import (code_width, kv_token_side_bytes,
                                    scheme_levels)
        tok_bytes = kv_token_side_bytes(dh)
        # f32 codebook padded to the code width's power of two (spx.codebook)
        lut_bytes = 4 * (1 << code_width(scheme_levels(kv_scheme)))
    else:
        tok_bytes, lut_bytes = dh * act_bytes, 0
    return _plan_fused_decode_cached(dh, rep, w, page_size, act_bytes,
                                     tok_bytes, lut_bytes,
                                     hw or current_hw())


def fused_decode_key(b: int, hkv: int, rep: int, w: int, dh: int,
                     page_size: int, max_pages: int,
                     kv_scheme: str | None) -> tuple:
    """Measured-autotune / plan cache key for one megakernel workload.

    ``kv_scheme`` and the window ``w`` (spec K+1) are deliberately part of
    the key: a winner measured for a dense pool must not be reused for a
    codes+scale pool of identical shape (different bytes/page, different
    in-kernel dequant work), and a plain-decode winner (w=1) must not leak
    into the verify window's workload (w=K+1) — they share every array
    shape except the query rows.
    """
    return ("paged_decode_ragged", b, hkv, rep, w, dh, page_size,
            max_pages, kv_scheme)


# ---------------------------------------------------------------------------
# Measured autotuning (env/flag-gated)
# ---------------------------------------------------------------------------

_MEASURED: dict = {}


def autotune_enabled() -> bool:
    """True when ``REPRO_AUTOTUNE`` is set to 1/true/measured. Read from
    the environment on every call (no caching) so tests can flip it."""
    return os.environ.get("REPRO_AUTOTUNE", "").lower() in ("1", "true",
                                                            "measured")


def measured_plan(key):
    """Previously measured winner for this shape key, or None. Consulted at
    trace time too (shapes are concrete there), so a winner measured during
    an eager warm-up call applies to every later jitted step. The measured
    table is process-local and cleared by ``clear_plan_cache()``."""
    return _MEASURED.get(key)


def measured_best(key, plans, runner: Callable[[object], float]):
    """Time each candidate plan with ``runner`` (seconds per call on the
    real kernel + real arrays) and cache the winner per shape key. The ops
    wrappers call this only when ``autotune_enabled()``; the analytical
    plan always seeds the candidate list so measurement can only improve
    on it."""
    if key in _MEASURED:
        return _MEASURED[key]
    best, best_t = None, float("inf")
    for plan in plans:
        try:
            t = runner(plan)
        except Exception as e:     # candidate doesn't compile on this target
            print(f"[planner] autotune candidate {plan} failed: {e!r}")
            continue
        if t < best_t:
            best, best_t = plan, t
    if best is None:
        # nothing measured: return the analytical seed WITHOUT caching so a
        # transient failure doesn't pin a known-bad plan for the process
        return plans[0]
    _MEASURED[key] = best
    return best


def clear_plan_cache():
    """Drop every cached plan: analytical matmul/attention/page plans AND
    measured-autotune winners. Needed after changing a ``REPRO_*`` planner
    env var mid-process — plans are cached per shape, not per environment."""
    _plan_matmul_cached.cache_clear()
    _plan_attention_cached.cache_clear()
    _plan_kv_pages_cached.cache_clear()
    _plan_shard_budget_cached.cache_clear()
    _MEASURED.clear()
