"""Public quantized-matmul / attention entry points, dispatched through the
execution-plan runtime (DESIGN.md §7).

Every impl of each op registers itself in ``repro.runtime.registry`` with an
availability predicate; ``spx_matmul`` / ``flash_attention`` resolve the
impl once (cached per backend) and fetch block shapes from
``repro.runtime.planner`` — the per-shape analytical solution of the
paper's §3.1 load-vs-compute inequality — instead of the old hard-coded
one-size-fits-all tiles and per-callsite string matching.

``impl`` semantics (see registry docstring): auto | pallas | interpret | ref.
"""
from __future__ import annotations

import collections
import functools
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import spx
from repro.core.quantized import QuantizedTensor
from repro.runtime import planner, registry

from . import ref as ref_impl
from .decode import LOOKUPS
from .flash_attention import flash_attention_pallas
from .paged_attention import (paged_decode_ragged_pallas,
                              paged_decode_ragged_quant_pallas)
from .paged_kv_write import paged_kv_write_pallas
from .spx_expert_matmul import spx_expert_matmul_pallas
from .spx_matmul import spx_matmul_pallas

__all__ = ["spx_matmul", "spx_expert_matmul", "flash_attention",
           "paged_decode_ragged", "paged_kv_write",
           "resolve_impl",
           "op_calls", "op_impls", "lut_lookups", "reset_op_calls"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# ---------------------------------------------------------------------------
# Trace-time call accounting: each public wrapper bumps its op counter on
# every call. Under jit the wrapper body runs at TRACE time only, so the
# counter reads wrapper calls in the traces made since the last reset — not
# launches, and nothing for a step whose trace was cached by an earlier
# caller in the process. Launches are counted where they happen, by the
# serving engine (``metrics()["launches"]``).
# ---------------------------------------------------------------------------

_OP_CALLS: collections.Counter = collections.Counter()
#: the same calls keyed by (op, impl that ran): an accelerator impl that
#: handed a shape to the oracle shows up under "ref"
_OP_IMPLS: collections.Counter = collections.Counter()


def op_calls() -> dict[str, int]:
    """Wrapper-call counts per op since the last ``reset_op_calls()``."""
    return dict(_OP_CALLS)


def op_impls() -> dict[tuple[str, str], int]:
    """Wrapper-call counts per (op, impl that ran) since the last
    ``reset_op_calls()``; the quantized-pool decode counts as
    ``paged_decode_ragged_quant``."""
    return dict(_OP_IMPLS)


def lut_lookups() -> dict[tuple[str, str], int]:
    """Kernel builds per (kernel, codebook lookup it compiled with:
    "gather" or "select") since the last ``reset_op_calls()``; counted
    at trace time like ``op_impls``, so a cached trace adds nothing."""
    return dict(LOOKUPS)


def reset_op_calls() -> None:
    _OP_CALLS.clear()
    _OP_IMPLS.clear()
    LOOKUPS.clear()


def _whole_per_device(fn, mesh):
    """``fn`` (a Pallas kernel call) for a jitted program over ``mesh``.
    GSPMD cannot partition a Mosaic kernel, so under a mesh of several
    devices every device runs the whole call: a shard_map with replicated
    specs gathers sharded operands first. Splitting the kernel's work
    across the mesh is not done here."""
    if mesh is None or mesh.size == 1:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def _per_kv_head(fn, mesh, axis, n_kv_heads: int, in_heads, out_heads):
    """``fn`` (a Pallas kernel call whose work splits by KV head) for a
    jitted program over ``mesh``. Where the mesh's ``axis`` divides the
    ``n_kv_heads`` heads, the serving pools are head-sharded over it
    (``ShardingPolicy.paged_state_specs``): a shard_map hands each device
    its own heads of every operand, so the pools are read and written
    where they lie and nothing is gathered. ``in_heads``/``out_heads``
    give each operand's (and result's) head dimension, None for one
    every device holds whole; they follow the pytree structure of the
    arguments and result. Otherwise as ``_whole_per_device``."""
    if mesh is None or mesh.size == 1:
        return fn
    n = dict(mesh.shape).get(axis, 1) if axis is not None else 1
    if n == 1 or n_kv_heads % n:
        return _whole_per_device(fn, mesh)

    def spec(d):
        return P() if d is None else P(*([None] * d), axis)

    specs = lambda tree: jax.tree_util.tree_map(spec, tree,
                                                is_leaf=lambda d: d is None)
    return jax.shard_map(fn, mesh=mesh, in_specs=specs(in_heads),
                         out_specs=specs(out_heads), check_vma=False)


def resolve_impl(impl: str) -> str:
    """Deprecated shim (one PR): impl-name resolution now lives in
    repro.runtime.registry; kept for callers that only need the name."""
    return registry.resolve("spx_matmul", impl).impl


# ---------------------------------------------------------------------------
# spx_matmul: x2 (M, K) @ dequant(qt (K, N)) — registered impls share the
# signature fn(x2, qt, scale, *, plan, out_dtype, ...)
# ---------------------------------------------------------------------------

@registry.register("spx_matmul", "ref",
                   priority=registry.PRIORITY_REFERENCE)
def _spx_matmul_ref(x2, qt: QuantizedTensor, scale, *, plan, out_dtype):
    del plan
    return ref_impl.spx_matmul_ref(x2, qt.codes, scale, qt.lut,
                                   packed=qt.packed, out_dtype=out_dtype)


def _spx_matmul_planned(x2, qt: QuantizedTensor, scale, *, plan, out_dtype,
                        interpret: bool):
    m = x2.shape[0]
    bm_eff = min(plan.bm, m)
    pad_m = (-m) % bm_eff
    if pad_m:
        x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
    out = spx_matmul_pallas(
        x2, qt.codes, scale, qt.lut, packed=qt.packed,
        bm=bm_eff, bn=plan.bn, bk=plan.bk, out_dtype=out_dtype,
        interpret=interpret)
    return out[:m] if pad_m else out


registry.register("spx_matmul", "pallas",
                  priority=registry.PRIORITY_ACCELERATOR,
                  available=_on_tpu)(
    functools.partial(_spx_matmul_planned, interpret=False))
registry.register("spx_matmul", "interpret",
                  priority=registry.PRIORITY_DEBUG)(
    functools.partial(_spx_matmul_planned, interpret=True))


def spx_matmul(x: jax.Array, qt: QuantizedTensor, *, impl: str = "auto",
               out_dtype=None, mesh=None) -> jax.Array:
    """x: (..., K) @ dequant(qt: (K, N)) -> (..., N). ``mesh``: the
    device mesh the enclosing jitted program is partitioned over, if any
    (a Pallas kernel then runs whole on every device)."""
    _OP_CALLS["spx_matmul"] += 1
    entry = registry.resolve("spx_matmul", impl)
    k_dim, n_dim = qt.logical_shape
    scale = qt.scale.reshape(1, n_dim).astype(jnp.float32)

    if entry.impl == "ref":
        # NO reshape: dot_general contracts x's last dim directly, so a
        # (batch@data, seq@model, K) sharding survives — flattening to 2-D
        # merges differently-sharded dims and forces a full gather
        # (measured 16x replicated linear-layer compute, EXPERIMENTS.md
        # §Perf cell 2)
        _OP_IMPLS["spx_matmul", "ref"] += 1
        return entry.fn(x, qt, scale, plan=None, out_dtype=out_dtype)

    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    m = x2.shape[0]
    plan = planner.plan_matmul(m, k_dim, n_dim, weight_bits=qt.bits,
                               act_bytes=x.dtype.itemsize, packed=qt.packed)
    if plan is None:
        if entry.impl == "pallas":
            # on the chip the oracle would hide that the kernel never ran
            raise ValueError(
                f"spx_matmul: no legal Pallas tiling for (M={m}, "
                f"K={k_dim}, N={n_dim}, packed={qt.packed}); pad the "
                "weight to 128-multiples or run impl='ref'")
        # interpret mode (CPU debugging): ragged dims run the oracle
        _OP_IMPLS["spx_matmul", "ref"] += 1
        out = ref_impl.spx_matmul_ref(x2, qt.codes, scale, qt.lut,
                                      packed=qt.packed, out_dtype=out_dtype)
        return out.reshape(*lead, n_dim)
    if entry.impl == "pallas" and planner.autotune_enabled():
        # dtype is part of the key: load time and VMEM fit depend on the
        # activation byte width, so an f32-tuned winner must not be reused
        # for a shape-identical bf16 call
        key = ("spx_matmul", m, k_dim, n_dim, qt.bits, qt.packed,
               x.dtype.itemsize)
        measured = planner.measured_plan(key)
        if measured is not None:
            # shape keys are concrete even at trace time, so a winner
            # measured during an eager warm-up applies inside jitted steps
            plan = measured
        elif not isinstance(x2, jax.core.Tracer):
            # measure on concrete arrays only: under an outer jit the
            # runner would time abstract tracing, not kernel execution,
            # and cache a garbage plan
            plan = _autotune_matmul(key, entry, x2, qt, scale, plan,
                                    out_dtype)
    _OP_IMPLS["spx_matmul", entry.impl] += 1
    out = _whole_per_device(
        functools.partial(entry.fn, plan=plan, out_dtype=out_dtype),
        mesh)(x2, qt, scale)
    return out.reshape(*lead, n_dim)


# ---------------------------------------------------------------------------
# spx_expert_matmul: rows grouped by expert in tiles of bm, each tile
# against its expert's SPx weight — registered impls share the signature
# fn(x, qt, scale, tile_expert, tile_rows, *, bm, plan, out_dtype)
# ---------------------------------------------------------------------------

@registry.register("spx_expert_matmul", "ref",
                   priority=registry.PRIORITY_REFERENCE)
def _spx_expert_matmul_ref(x, qt: QuantizedTensor, scale, tile_expert,
                           tile_rows, *, bm, plan, out_dtype):
    del plan
    return ref_impl.spx_expert_matmul_ref(
        x, qt.codes, scale, qt.lut, tile_expert, tile_rows, bm=bm,
        packed=qt.packed, out_dtype=out_dtype)


def _spx_expert_matmul_planned(x, qt: QuantizedTensor, scale, tile_expert,
                               tile_rows, *, bm, plan, out_dtype,
                               interpret: bool):
    return spx_expert_matmul_pallas(
        x, qt.codes, scale, qt.lut, tile_expert, tile_rows,
        packed=qt.packed, bm=bm, bn=plan.bn, bk=plan.bk,
        out_dtype=out_dtype, interpret=interpret)


registry.register("spx_expert_matmul", "pallas",
                  priority=registry.PRIORITY_ACCELERATOR,
                  available=_on_tpu)(
    functools.partial(_spx_expert_matmul_planned, interpret=False))
registry.register("spx_expert_matmul", "interpret",
                  priority=registry.PRIORITY_DEBUG)(
    functools.partial(_spx_expert_matmul_planned, interpret=True))


def spx_expert_matmul(x: jax.Array, qt: QuantizedTensor,
                      tile_expert: jax.Array, tile_rows: jax.Array, *,
                      bm: int, impl: str = "auto", out_dtype=None,
                      mesh=None) -> jax.Array:
    """x: (G * bm, K) rows grouped by expert in G tiles of ``bm`` rows;
    tile g multiplies dequant(qt[tile_expert[g]]) with qt: (E, K, N)
    SPx codes. ``tile_rows`` (G,) int32: rows of each tile that carry a
    pair; a tile with none comes back zero. Returns (G * bm, N).
    ``mesh`` as in ``spx_matmul``."""
    _OP_CALLS["spx_expert_matmul"] += 1
    entry = registry.resolve("spx_expert_matmul", impl)
    n_e, k_dim, n_dim = qt.logical_shape
    scale = qt.scale.reshape(n_e, 1, n_dim).astype(jnp.float32)
    tile_expert = jnp.asarray(tile_expert, jnp.int32)
    tile_rows = jnp.asarray(tile_rows, jnp.int32)
    plan = None
    if entry.impl != "ref":
        plan = planner.plan_matmul(bm, k_dim, n_dim, weight_bits=qt.bits,
                                   act_bytes=x.dtype.itemsize,
                                   packed=qt.packed)
        if plan is None:
            if entry.impl == "pallas":
                raise ValueError(
                    f"spx_expert_matmul: no legal Pallas tiling for "
                    f"(bm={bm}, K={k_dim}, N={n_dim}, packed={qt.packed})")
            entry = registry.resolve("spx_expert_matmul", "ref")
    _OP_IMPLS["spx_expert_matmul", entry.impl] += 1
    fn = functools.partial(entry.fn, bm=bm, plan=plan, out_dtype=out_dtype)
    if entry.impl != "ref":
        fn = _whole_per_device(fn, mesh)
    return fn(x, qt, scale, tile_expert, tile_rows)


def _autotune_matmul(key, entry, x2, qt, scale, plan, out_dtype):
    """Measured refinement over divisor-legal candidates near the
    analytical plan (env-gated; see planner.autotune_enabled)."""
    m = x2.shape[0]
    k_dim, n_dim = qt.logical_shape
    bm_c, bn_c, bk_c = planner.matmul_candidates(m, k_dim, n_dim,
                                                 packed=qt.packed)
    cands = [plan] + [
        planner.MatmulBlocks(bm, bn, bk, False, 0.0, 0)
        for bm in bm_c[:3] for bn in bn_c[:3] for bk in bk_c[:3]
        if (bm, bn, bk) != (plan.bm, plan.bn, plan.bk)]

    def runner(p):
        f = lambda: entry.fn(x2, qt, scale, plan=p, out_dtype=out_dtype)
        jax.block_until_ready(f())         # compile + warm
        t0 = time.perf_counter()
        jax.block_until_ready(f())
        return time.perf_counter() - t0

    return planner.measured_best(key, cands, runner)


# ---------------------------------------------------------------------------
# flash_attention: qf/kf/vf (B*H, S, dh) — registered impls share the
# signature fn(qf, kf, vf, *, causal, plan)
# ---------------------------------------------------------------------------

@registry.register("flash_attention", "ref",
                   priority=registry.PRIORITY_REFERENCE)
def _flash_attention_ref(qf, kf, vf, *, causal, plan):
    del plan
    return ref_impl.attention_ref(qf, kf, vf, causal=causal)


def _flash_attention_planned(qf, kf, vf, *, causal, plan, interpret: bool):
    return flash_attention_pallas(qf, kf, vf, causal=causal, bq=plan.bq,
                                  bkv=plan.bkv, interpret=interpret)


registry.register("flash_attention", "pallas",
                  priority=registry.PRIORITY_ACCELERATOR,
                  available=_on_tpu)(
    functools.partial(_flash_attention_planned, interpret=False))
registry.register("flash_attention", "interpret",
                  priority=registry.PRIORITY_DEBUG)(
    functools.partial(_flash_attention_planned, interpret=True))


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, impl: str = "auto",
                    mesh=None) -> jax.Array:
    """GQA attention. q: (B, Hq, Sq, dh); k, v: (B, Hkv, Skv, dh);
    Hq % Hkv == 0. Returns (B, Hq, Sq, dh). ``mesh`` as in
    ``spx_matmul``."""
    _OP_CALLS["flash_attention"] += 1
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    rep = hq // hkv
    entry = registry.resolve("flash_attention", impl)

    if rep > 1:
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    qf = q.reshape(b * hq, sq, dh)
    kf = k.reshape(b * hq, skv, dh)
    vf = v.reshape(b * hq, skv, dh)

    if entry.impl == "ref":
        _OP_IMPLS["flash_attention", "ref"] += 1
        return entry.fn(qf, kf, vf, causal=causal, plan=None).reshape(q.shape)

    plan = planner.plan_attention(sq, skv, dh, act_bytes=q.dtype.itemsize)
    if plan is None:
        if entry.impl == "pallas":
            raise ValueError(
                f"flash_attention: no legal Pallas tiling for (Sq={sq}, "
                f"Skv={skv}); pad the sequence to a multiple of 8 or run "
                "impl='ref'")
        _OP_IMPLS["flash_attention", "ref"] += 1
        return ref_impl.attention_ref(qf, kf, vf,
                                      causal=causal).reshape(q.shape)
    _OP_IMPLS["flash_attention", entry.impl] += 1
    out = _whole_per_device(
        functools.partial(entry.fn, causal=causal, plan=plan), mesh)(
            qf, kf, vf)
    return out.reshape(q.shape)


# ---------------------------------------------------------------------------
# paged_decode_ragged: the decode megakernel — one launch covers the whole
# batched decode tick, plain decode AND the spec-decode verify window, over
# a ragged (slot, attend_len) grid, for dense or quantized (fused-LUT) KV
# pools. Registered impls share the signatures
#   dense: fn(q4, k_pages, v_pages, block_table, ctx_len, q_len, layer, *, w)
#   quant: fn(q4, k_codes, k_scale, v_codes, v_scale, block_table, ctx_len,
#             q_len, layer, lut, *, w)
# with q4: (B, Hkv, rep * w, dh) rep-major window rows, pools stacked over
# layers and layer: (1,) int32.
# ---------------------------------------------------------------------------

@registry.register("paged_decode_ragged", "ref",
                   priority=registry.PRIORITY_REFERENCE)
def _paged_decode_ragged_ref(q4, k_pages, v_pages, block_table, ctx_len,
                             q_len, layer, *, w):
    return ref_impl.paged_decode_ragged_ref(q4, k_pages[layer[0]],
                                            v_pages[layer[0]], block_table,
                                            ctx_len, q_len, w=w)


registry.register("paged_decode_ragged", "pallas",
                  priority=registry.PRIORITY_ACCELERATOR,
                  available=_on_tpu)(
    functools.partial(paged_decode_ragged_pallas, interpret=False))
registry.register("paged_decode_ragged", "interpret",
                  priority=registry.PRIORITY_DEBUG)(
    functools.partial(paged_decode_ragged_pallas, interpret=True))


@registry.register("paged_decode_ragged_quant", "ref",
                   priority=registry.PRIORITY_REFERENCE)
def _paged_decode_ragged_quant_ref(q4, k_codes, k_scale, v_codes, v_scale,
                                   block_table, ctx_len, q_len, layer, lut,
                                   *, w):
    l = layer[0]
    return ref_impl.paged_decode_ragged_quant_ref(
        q4, k_codes[l], k_scale[l], v_codes[l], v_scale[l], block_table,
        ctx_len, q_len, lut, w=w)


registry.register("paged_decode_ragged_quant", "pallas",
                  priority=registry.PRIORITY_ACCELERATOR,
                  available=_on_tpu)(
    functools.partial(paged_decode_ragged_quant_pallas, interpret=False))
registry.register("paged_decode_ragged_quant", "interpret",
                  priority=registry.PRIORITY_DEBUG)(
    functools.partial(paged_decode_ragged_quant_pallas, interpret=True))


def paged_decode_ragged(q: jax.Array, k_pages, v_pages,
                        block_table: jax.Array, ctx_len: jax.Array,
                        q_len: jax.Array, *, layer,
                        kv_scheme: str | None = None,
                        impl: str = "auto", mesh=None,
                        head_axis: str | None = None) -> jax.Array:
    """Ragged decode-window attention in ONE kernel launch per tick.

    q: (B, W, Hq, dh) — W window positions per slot (spec K+1, or 1 for
    plain decode; static). q_len: (B,) int32 valid window rows per slot —
    the ragged part; rows at positions >= q_len return exact zeros.
    ctx_len: (B,) int32 tokens already in the pages before this window
    (window position i of slot b attends cache positions <= ctx_len[b] +
    i; ctx_len = q_len = 0 marks an inactive slot, which skips every
    page). k_pages/v_pages: every layer's pools, stacked on axis 0 —
    either the dense (P, n_pages, Hkv, page_size, dh) pools or the
    quantized {"codes", "scale"} dicts from
    ``nn.attention.paged_kv_write`` — a dict pool routes to the fused-LUT
    variant, with ``kv_scheme`` naming the codebook (required then).
    layer: int32 scalar, the layer read. Returns (B, W, Hq, dh).

    The per-slot attend_len = ctx_len + q_len drives the kernel's page
    loop trip count directly — no pow2 window padding, so varying
    attend_len across ticks never retraces. Block tables and both length
    vectors ride as scalar prefetch. Under a ``mesh`` whose ``head_axis``
    divides Hkv, each device attends with its own KV heads of the
    head-sharded pools; otherwise as in ``spx_matmul``.
    """
    _OP_CALLS["paged_decode_ragged"] += 1
    quant = isinstance(k_pages, dict)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    b, w, hq, dh = q.shape
    hkv = (k_pages["codes"] if quant else k_pages).shape[2]
    assert hq % hkv == 0, (hq, hkv)
    rep = hq // hkv
    # (B, W, Hq, dh) -> (B, Hkv, rep * w, dh), rep-major: row r * w + i is
    # window position i of query head r under this KV head
    q4 = jnp.moveaxis(q.reshape(b, w, hkv, rep, dh), 1, 3) \
            .reshape(b, hkv, rep * w, dh)
    block_table = jnp.asarray(block_table, jnp.int32)
    ctx_len = jnp.asarray(ctx_len, jnp.int32)
    q_len = jnp.asarray(q_len, jnp.int32)

    op = "paged_decode_ragged_quant" if quant else "paged_decode_ragged"
    entry = registry.resolve(op, impl)
    _OP_IMPLS[op, entry.impl] += 1
    if quant:
        if kv_scheme is None:
            raise ValueError("quantized KV pools need kv_scheme for the "
                             "in-kernel codebook")
        lut = spx.codebook(spx.scheme_levels(kv_scheme), dtype=jnp.float32)
        args = (q4, k_pages["codes"], k_pages["scale"], v_pages["codes"],
                v_pages["scale"], block_table, ctx_len, q_len, layer, lut)
        heads = (1, 2, 2, 2, 2) + (None,) * 5
        page_size = k_pages["codes"].shape[3]
    else:
        args = (q4, k_pages, v_pages, block_table, ctx_len, q_len, layer)
        heads = (1, 2, 2) + (None,) * 4
        page_size = k_pages.shape[3]

    if entry.impl == "pallas" and planner.autotune_enabled():
        # keyed per workload INCLUDING kv_scheme and the window w (spec
        # K+1): dense vs codes+scale pools and decode vs verify windows
        # share array shapes but not cost — winners must not collide
        key = planner.fused_decode_key(b, hkv, rep, w, dh, page_size,
                                       block_table.shape[1], kv_scheme)
        if planner.measured_plan(key) is None \
                and not isinstance(q4, jax.core.Tracer):
            plan = planner.plan_fused_decode(
                dh, rep=rep, w=w, page_size=page_size,
                act_bytes=q.dtype.itemsize, kv_scheme=kv_scheme)

            def runner(p):
                del p
                f = lambda: entry.fn(*args, w=w)
                jax.block_until_ready(f())     # compile + warm
                t0 = time.perf_counter()
                jax.block_until_ready(f())
                return time.perf_counter() - t0

            planner.measured_best(key, [plan], runner)

    fn = functools.partial(entry.fn, w=w)
    if entry.impl != "ref":
        fn = _per_kv_head(fn, mesh, head_axis, hkv, heads, 1)
    out = fn(*args)
    # inverse of the rep-major packing
    return jnp.moveaxis(out.reshape(b, hkv, rep, w, dh), 3, 1) \
              .reshape(b, w, hq, dh)


# ---------------------------------------------------------------------------
# paged_kv_write: a step's new K/V rows into the stacked page pools, in
# place — registered impls share the signature
#   fn(pools, rows, layer, block_table, ctx_len, n_valid)
# ---------------------------------------------------------------------------

registry.register("paged_kv_write", "ref",
                  priority=registry.PRIORITY_REFERENCE)(
    ref_impl.paged_kv_write_ref)
registry.register("paged_kv_write", "pallas",
                  priority=registry.PRIORITY_ACCELERATOR,
                  available=_on_tpu)(
    functools.partial(paged_kv_write_pallas, interpret=False))
registry.register("paged_kv_write", "interpret",
                  priority=registry.PRIORITY_DEBUG)(
    functools.partial(paged_kv_write_pallas, interpret=True))


def paged_kv_write(pools, rows, layer, block_table: jax.Array,
                   ctx_len: jax.Array, n_valid: jax.Array, *,
                   impl: str = "auto", mesh=None,
                   head_axis: str | None = None) -> list:
    """Write rows ``i < n_valid[b]`` of each slot's C-row window to token
    positions ``ctx_len[b] + i`` of ``pools[k][layer]``; rows past
    ``n_valid`` (chunk padding, inactive slots) are not written.

    pools: every layer's pool leaves, stacked on axis 0 — each
    ``(P, n_pages, Hkv, page_size, w)`` with rows ``(B, C, Hkv, w)``, or
    ``(P, n_pages, Hkv, lanes)`` holding one value a token at lane
    ``offset`` with rows ``(B, C, Hkv)`` (the SPx scales); the first leaf
    is of the first kind. layer: int32 scalar; block_table: (B,
    max_pages) int32. A written page belongs to one slot. The Pallas impl
    updates the pools in place (aliased); returns the updated leaves.
    ``mesh``/``head_axis`` as in ``paged_decode_ragged``: every leaf and
    row block has its KV heads on axis 2."""
    _OP_CALLS["paged_kv_write"] += 1
    entry = registry.resolve("paged_kv_write", impl)
    _OP_IMPLS["paged_kv_write", entry.impl] += 1
    fn = entry.fn
    if entry.impl != "ref":
        n = len(pools)
        fn = _per_kv_head(lambda *a: tuple(entry.fn(*a)), mesh, head_axis,
                          pools[0].shape[2],
                          ([2] * n, [2] * n, None, None, None, None),
                          (2,) * n)
    return list(fn(list(pools), list(rows), jnp.asarray(layer, jnp.int32),
                   jnp.asarray(block_table, jnp.int32),
                   jnp.asarray(ctx_len, jnp.int32),
                   jnp.asarray(n_valid, jnp.int32)))
