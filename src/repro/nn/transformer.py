"""Block assembly and the scan-over-periods stack.

An architecture is a repeating *pattern* of slots (ArchConfig.pattern), e.g.
  dense transformer : ("attn+dense",)
  MoE transformer   : ("attn+moe",)
  Jamba period      : ("attn+moe", "mamba+dense", "mamba+moe", ... ) x8
  xLSTM period      : ("mlstm", "mlstm", "mlstm", "slstm+dense")
Parameters for each slot are stacked over periods (leading P dim) and the
stack scans over periods — HLO stays O(pattern), not O(n_layers), which keeps
the 512-device dry-run compile tractable for 61-layer/1T-param configs.

Decode carries per-slot state (KV caches / SSM states), also stacked over
periods and threaded through the same scan.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core.spx import kv_scale_lanes
from repro.runtime import Runtime

from . import ssm
from .attention import (_apply_positional, _project_qkv, attention_core,
                        attn_apply_dense, attn_decode_step, attn_init,
                        attn_paged_step)
from .layers import norm_apply, norm_init, opt_barrier
from .mlp import mlp_apply, mlp_init
from .moe import moe_apply, moe_init, moe_serve

__all__ = ["stack_init", "stack_apply", "stack_prefill", "stack_decode",
           "stack_paged", "slot_init_cache", "slot_init_paged_cache",
           "SLOT_KINDS"]

SLOT_KINDS = ("attn", "xdec", "mamba", "mlstm", "slstm")


def _rope_theta(cfg: ArchConfig):
    """The attention layers' RoPE base, or None for NoPE attention."""
    return None if cfg.nope else cfg.rope_theta


def _parse_slot(slot: str):
    parts = slot.split("+")
    mixer = parts[0]
    ffn = parts[1] if len(parts) > 1 else None
    assert mixer in SLOT_KINDS, slot
    return mixer, ffn


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def _slot_init(key, slot: str, cfg: ArchConfig, dtype) -> dict:
    mixer, ffn = _parse_slot(slot)
    ks = jax.random.split(key, 6)
    d = cfg.d_model
    p: dict[str, Any] = {"norm1": norm_init(cfg.norm, d, dtype)}
    if mixer in ("attn", "xdec"):
        p["attn"] = attn_init(ks[0], d, cfg.n_heads, cfg.n_kv_heads, cfg.dh,
                              qkv_bias=cfg.qkv_bias, dtype=dtype)
        if mixer == "xdec":
            p["xattn"] = attn_init(ks[3], d, cfg.n_heads, cfg.n_kv_heads,
                                   cfg.dh, qkv_bias=cfg.qkv_bias, dtype=dtype)
            p["norm_x"] = norm_init(cfg.norm, d, dtype)
    elif mixer == "mamba":
        p["mamba"] = ssm.mamba_init(
            ks[0], d, d_state=cfg.ssm_d_state, d_conv=cfg.ssm_d_conv,
            expand=cfg.ssm_expand, dt_rank=cfg.ssm_dt_rank or None,
            bcdt_norm=cfg.ssm_bcdt_norm, dtype=dtype)
    elif mixer == "mlstm":
        p["mlstm"] = ssm.mlstm_init(ks[0], d, n_heads=cfg.lstm_heads,
                                    expand=cfg.ssm_expand,
                                    d_conv=cfg.ssm_d_conv, dtype=dtype)
    elif mixer == "slstm":
        p["slstm"] = ssm.slstm_init(ks[0], d, n_heads=cfg.lstm_heads,
                                    dtype=dtype)
    if ffn == "dense":
        p["norm2"] = norm_init(cfg.norm, d, dtype)
        # d_ff=0 (xLSTM assignment): blocks carry their own projections; the
        # sLSTM slot still gets a 4/3-expansion FFN per the xLSTM paper
        d_ff = cfg.d_ff or ((4 * d // 3 + 127) // 128 * 128)
        p["mlp"] = mlp_init(ks[1], d, d_ff, variant=cfg.mlp_variant,
                            act=cfg.act, dtype=dtype)
    elif ffn == "moe":
        p["norm2"] = norm_init(cfg.norm, d, dtype)
        p["moe"] = moe_init(ks[1], d, cfg.d_ff, cfg.n_experts,
                            n_shared=cfg.n_shared_experts, dtype=dtype)
    return p


def stack_init(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    """Stacked params: {'slots': [slot_pytree(P, ...), ...]}.

    Each slot's periods initialize under ``vmap`` over their keys — the
    same values as initializing period by period and stacking, without
    holding every period's copy next to the stack (at full width that
    would double the f32 weights on the device)."""
    n_p = cfg.n_periods
    slots = []
    for j, slot in enumerate(cfg.pattern):
        keys = jax.random.split(jax.random.fold_in(key, j), n_p)
        slots.append(jax.vmap(
            lambda k, slot=slot: _slot_init(k, slot, cfg, dtype))(keys))
    return {"slots": slots}


# ---------------------------------------------------------------------------
# Apply — train / prefill / decode share one slot dispatcher
# ---------------------------------------------------------------------------

def _cross_kv(p_attn: dict, enc_out: jax.Array, n_kv_heads: int,
              head_dim: int, rt: Runtime):
    """Per-layer cross-attention K/V projections of the encoder output."""
    from .layers import dense_apply
    b, s, _ = enc_out.shape
    k = dense_apply(p_attn["wk"], enc_out, rt).reshape(b, s, n_kv_heads,
                                                       head_dim)
    v = dense_apply(p_attn["wv"], enc_out, rt).reshape(b, s, n_kv_heads,
                                                       head_dim)
    return k, v


def _slab_step(cache: dict, layer, state_idx, n_valid, step_fn):
    """Run an SSM paged step against the slab region: gather each row's
    slab (``state_idx[:, 0]``) of ``layer``, step, scatter the new state
    back in place. Rows with ``n_valid == 0`` (inactive slots in a mixed
    prefill/decode tick) and rows whose slab index is the out-of-range
    sentinel are dropped by the scatter — their slabs stay bit-identical
    (the step itself is also identity-masked, so this is belt and
    braces). ``cache``: per-slot state leaves of every layer, shaped
    (P, n_slabs, ...)."""
    slab_idx = state_idx[:, 0].astype(jnp.int32)
    n_slabs = next(iter(cache.values())).shape[1]
    safe = jnp.clip(slab_idx, 0, max(n_slabs - 1, 0))
    state_b = {k: leaf[layer, safe] for k, leaf in cache.items()}
    y, ns = step_fn(state_b)
    dst = jnp.where(n_valid > 0, slab_idx, n_slabs)
    new_cache = {k: leaf.at[layer, dst].set(ns[k].astype(leaf.dtype),
                                            mode="drop")
                 for k, leaf in cache.items()}
    return y, new_cache


def _slot_apply(slot: str, p: dict, x, positions, cfg: ArchConfig,
                rt: Runtime, *, mode: str, cache=None, pos=None,
                enc_out=None, causal: bool = True, paged_ctx=None,
                fused: bool = False, layer=None):
    """mode: 'train' | 'prefill' | 'decode' | 'paged'. Returns
    (x, new_cache, aux). Paged mode (serving: chunked prefill + paged
    decode through one path) takes ``paged_ctx = (ctx_len, block_table,
    n_valid, state_idx)``, the slot's regions of every period as
    ``cache`` and the period index ``layer``, and routes per *slot kind*:
    attention and decoder self-attention write token pages, SSM mixers
    read/write their row of the slab region (``state_idx[:, 0]``),
    cross-attention reads the shared read-only cross region
    (``state_idx[:, 1]``) — one state-cache, heterogeneous layers, each
    region read and written in place at ``layer``."""
    mixer, ffn = _parse_slot(slot)
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache

    h = norm_apply(cfg.norm, p["norm1"], x)
    if mode == "paged":
        ctx_len, block_table, n_valid, state_idx = paged_ctx
    if mixer in ("attn", "xdec"):
        if mode == "paged":
            with jax.named_scope("attention"):
                y, new_cache = attn_paged_step(
                    p["attn"], h, ctx_len, block_table, cache, layer,
                    n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                    head_dim=cfg.dh, n_valid=n_valid,
                    rope_theta=_rope_theta(cfg),
                    mrope_sections=cfg.mrope_sections, rt=rt, fused=fused)
        elif mode == "decode":
            y, kv = attn_decode_step(
                p["attn"], h, pos, (cache["k"], cache["v"]),
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.dh, rope_theta=_rope_theta(cfg),
                mrope_sections=cfg.mrope_sections, rt=rt)
            new_cache = dict(cache, k=kv[0], v=kv[1])
        elif mode == "prefill":
            y, (k, v) = attn_apply_dense(
                p["attn"], h, positions, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.dh, causal=causal,
                rope_theta=_rope_theta(cfg), mrope_sections=cfg.mrope_sections,
                rt=rt, kv_out=True)
            # write prefix into the (possibly longer) cache: (B,S,Hkv,dh) ->
            # (B,Hkv,S,dh) layout
            kT, vT = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)
            new_cache = dict(cache)

            def write(slot_cache, val):
                if isinstance(slot_cache, dict):   # quantized KV (rt.kv_scheme)
                    from .attention import quantize_kv
                    codes, scale = quantize_kv(val, rt.kv_scheme)
                    return {"codes": jax.lax.dynamic_update_slice_in_dim(
                                slot_cache["codes"], codes, 0, axis=2),
                            "scale": jax.lax.dynamic_update_slice_in_dim(
                                slot_cache["scale"], scale, 0, axis=2)}
                return jax.lax.dynamic_update_slice_in_dim(
                    slot_cache, val.astype(slot_cache.dtype), 0, axis=2)

            new_cache["k"] = write(cache["k"], kT)
            new_cache["v"] = write(cache["v"], vT)
        else:
            y = attn_apply_dense(
                p["attn"], h, positions, n_heads=cfg.n_heads,
                n_kv_heads=cfg.n_kv_heads, head_dim=cfg.dh, causal=causal,
                rope_theta=_rope_theta(cfg), mrope_sections=cfg.mrope_sections,
                rt=rt)
        x = x + y
        if mixer == "xdec":
            hx = norm_apply(cfg.norm, p["norm_x"], x)
            if mode == "paged":
                # cross-attention against the shared read-only cross
                # region: each row reads the encoder-output K/V entry its
                # sequence was mapped to at admission (state_idx[:, 1]);
                # entries are written once by the engine's encoder pass
                # and never mutated here. Matches the dense path: q is
                # roped at the absolute token position, K/V are unroped,
                # attention is non-causal over all encoder frames.
                bq, cq, _ = hx.shape
                n_cross = new_cache["xk"].shape[1]
                xs_idx = jnp.clip(state_idx[:, 1], 0,
                                  max(n_cross - 1, 0)).astype(jnp.int32)
                kh = new_cache["xk"][layer, xs_idx]  # (B, Hkv, S_enc, dh)
                vh = new_cache["xv"][layer, xs_idx]
                qx, kx, _ = _project_qkv(p["xattn"], hx, cfg.n_heads,
                                         cfg.n_kv_heads, cfg.dh, rt)
                positions_x = ctx_len[:, None] \
                    + jnp.arange(cq, dtype=jnp.int32)
                qx, _ = _apply_positional(qx, kx, positions_x,
                                          _rope_theta(cfg), None)
                o = attention_core(jnp.swapaxes(qx, 1, 2), kh, vh,
                                   causal=False, rt=rt)
                y = jnp.swapaxes(o, 1, 2).reshape(bq, cq,
                                                  cfg.n_heads * cfg.dh)
                from .layers import dense_apply
                y = dense_apply(p["xattn"]["wo"], y, rt)
            elif mode == "decode":
                xkv = (cache["xk"], cache["xv"])
                y, _ = attn_decode_step(
                    p["xattn"], hx, pos, None, n_heads=cfg.n_heads,
                    n_kv_heads=cfg.n_kv_heads, head_dim=cfg.dh,
                    rope_theta=_rope_theta(cfg), rt=rt,
                    cross_kv=(jnp.swapaxes(xkv[0], 1, 2),
                              jnp.swapaxes(xkv[1], 1, 2)))
            else:
                xk, xv = _cross_kv(p["xattn"], enc_out, cfg.n_kv_heads,
                                   cfg.dh, rt)
                y = attn_apply_dense(
                    p["xattn"], hx, positions, n_heads=cfg.n_heads,
                    n_kv_heads=cfg.n_kv_heads, head_dim=cfg.dh, causal=False,
                    rope_theta=_rope_theta(cfg), rt=rt, cross_kv=(xk, xv))
                if mode == "prefill":
                    new_cache = dict(new_cache,
                                     xk=jnp.swapaxes(xk, 1, 2)
                                     .astype(cache["xk"].dtype),
                                     xv=jnp.swapaxes(xv, 1, 2)
                                     .astype(cache["xv"].dtype))
            x = x + y
    elif mixer == "mamba":
        if mode == "paged":
            with jax.named_scope("mamba"):
                y, new_cache = _slab_step(
                    cache, layer, state_idx, n_valid,
                    lambda st: ssm.mamba_paged_step(p["mamba"], h, st,
                                                    n_valid, rt=rt))
        elif mode == "decode":
            y, new_cache = ssm.mamba_decode_step(p["mamba"], h, cache, rt=rt)
        elif mode == "prefill":
            y, new_cache = ssm.mamba_apply(p["mamba"], h, rt=rt,
                                           return_state=True)
        else:
            y = ssm.mamba_apply(p["mamba"], h, rt=rt)
        x = x + y
    elif mixer == "mlstm":
        if mode == "paged":
            y, new_cache = _slab_step(
                cache, layer, state_idx, n_valid,
                lambda st: ssm.mlstm_paged_step(p["mlstm"], h, st, n_valid,
                                                rt=rt,
                                                n_heads=cfg.lstm_heads))
        elif mode == "decode":
            y, new_cache = ssm.mlstm_decode_step(p["mlstm"], h, cache, rt=rt,
                                                 n_heads=cfg.lstm_heads)
        elif mode == "prefill":
            y, new_cache = ssm.mlstm_apply(p["mlstm"], h, rt=rt,
                                           n_heads=cfg.lstm_heads,
                                           return_state=True)
        else:
            y = ssm.mlstm_apply(p["mlstm"], h, rt=rt, n_heads=cfg.lstm_heads)
        x = x + y
    elif mixer == "slstm":
        if mode == "paged":
            y, new_cache = _slab_step(
                cache, layer, state_idx, n_valid,
                lambda st: ssm.slstm_paged_step(p["slstm"], h, st, n_valid,
                                                rt=rt))
        elif mode == "decode":
            y, new_cache = ssm.slstm_decode_step(p["slstm"], h, cache, rt=rt)
        elif mode == "prefill":
            y, new_cache = ssm.slstm_apply(p["slstm"], h, rt=rt,
                                           return_state=True)
        else:
            y = ssm.slstm_apply(p["slstm"], h, rt=rt)
        x = x + y

    if ffn == "dense":
        with jax.named_scope("mlp"):
            h = norm_apply(cfg.norm, p["norm2"], x)
            x = x + mlp_apply(p["mlp"], h, variant=cfg.mlp_variant,
                              act=cfg.act, rt=rt)
    elif ffn == "moe":
        h = norm_apply(cfg.norm, p["norm2"], x)
        if mode == "train":
            y, a = moe_apply(p["moe"], h, top_k=cfg.top_k,
                             n_experts=cfg.n_experts,
                             capacity_factor=cfg.capacity_factor,
                             renormalize=cfg.router_renorm, rt=rt)
            aux = aux + a
        else:
            # serving: dropless, SPx expert codes read in place
            y = moe_serve(p["moe"], h, top_k=cfg.top_k,
                          n_experts=cfg.n_experts,
                          renormalize=cfg.router_renorm, rt=rt)
        x = x + y
    return x, new_cache, aux


def _sp_constrain(x, rt: Runtime):
    """Sequence-parallel residual stream: between layers the (B, S, D) carry
    shards over the model axis on S. This is what keeps the remat'd carry
    stack (L x B x S x D) inside HBM at production batch sizes; GSPMD turns
    the layer-boundary transitions into reduce-scatter/all-gather pairs (the
    Megatron-SP pattern — same bytes as the TP all-reduce they replace)."""
    if rt.mesh is None or x.ndim != 3 or rt.model_axis is None:
        return x
    n_model = dict(rt.mesh.shape).get(rt.model_axis, 1)
    if x.shape[1] % n_model:
        return x
    from jax.sharding import NamedSharding
    dp = rt.data_axes if rt.data_axes else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rt.mesh, jax.sharding.PartitionSpec(
            dp, rt.model_axis, None)))


def _period_body(carry, xs, *, cfg: ArchConfig, rt: Runtime, mode: str,
                 positions=None, enc_out=None, causal: bool = True,
                 paged_ctx=None, fused: bool = False):
    layer = None
    if mode == "decode":
        x, pos, aux = carry
        slot_params, caches = xs
    elif mode == "prefill":
        x, aux = carry
        slot_params, caches = xs
        pos = None
    elif mode == "paged":
        # the regions of every period ride in the carry, indexed in place
        x, aux, caches = carry
        slot_params, layer = xs
        pos = None
    else:
        x, aux = carry
        slot_params, caches = xs, [None] * len(cfg.pattern)
        pos = None
        # keep the remat'd carry stack in the carry's own (bf16) dtype: the
        # barrier stops XLA fusing the first norm's f32 convert into the
        # residual-stack write (which would double its bytes)
        x = opt_barrier(x)
    new_caches = []
    for j, slot in enumerate(cfg.pattern):
        def run_slot(sp, xx, _slot=slot, _cache=caches[j]):
            if mode == "train":
                # keep the checkpoint-saved slot input in its own dtype
                # (block f32-convert fusion into the residual save)
                xx = opt_barrier(xx)
            return _slot_apply(_slot, sp, xx, positions, cfg, rt, mode=mode,
                               cache=_cache, pos=pos, enc_out=enc_out,
                               causal=causal, paged_ctx=paged_ctx,
                               fused=fused, layer=layer)
        if mode == "train" and rt.remat != "none" and len(cfg.pattern) > 1:
            # hierarchical remat: the period body is already checkpointed;
            # checkpointing each slot too keeps the backward's recompute
            # liveset to ONE slot (8 Jamba slots at d=8192 would otherwise
            # be live together during the period recompute)
            run_slot = jax.checkpoint(run_slot, prevent_cse=False)
        x, nc, a = run_slot(slot_params[j], x)
        new_caches.append(nc)
        aux = aux + a
    if mode != "decode":
        x = _sp_constrain(x, rt)
    if mode == "decode":
        return (x, pos, aux), new_caches
    if mode == "prefill":
        return (x, aux), new_caches
    if mode == "paged":
        return (x, aux, new_caches), None
    return (x, aux), None


def stack_apply(params: dict, x: jax.Array, positions, cfg: ArchConfig,
                rt: Runtime, enc_out=None, causal: bool = True,
                pattern: tuple | None = None):
    """Train-mode stack. Returns (x, aux_loss_sum)."""
    cfg_eff = cfg if pattern is None else _with_pattern(cfg, pattern)
    body = functools.partial(_period_body, cfg=cfg_eff, rt=rt, mode="train",
                             positions=positions, enc_out=enc_out,
                             causal=causal)
    if rt.remat != "none":
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if rt.remat == "dots" else None)
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                               tuple(params["slots"]),
                               unroll=True if rt.unroll else 1)
    return x, aux


def stack_prefill(params: dict, x: jax.Array, positions, cfg: ArchConfig,
                  rt: Runtime, caches, enc_out=None):
    """Prefill: like train but returns per-slot caches stacked over periods.
    ``caches`` are pre-allocated (full decode length) and the prefix is
    written in-place."""
    def body(carry, xs):
        return _period_body(carry, xs, cfg=cfg, rt=rt, mode="prefill",
                            positions=positions, enc_out=enc_out)
    (x, aux), new_caches = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)),
        (tuple(params["slots"]), tuple(caches)),
        unroll=True if rt.unroll else 1)
    return x, new_caches, aux


def stack_decode(params: dict, x: jax.Array, pos, cfg: ArchConfig,
                 rt: Runtime, caches):
    """One-token decode through all periods, threading caches."""
    def body(carry, xs):
        return _period_body(carry, xs, cfg=cfg, rt=rt, mode="decode")
    (x, _, aux), new_caches = jax.lax.scan(
        body, (x, pos, jnp.zeros((), jnp.float32)),
        (tuple(params["slots"]), tuple(caches)),
        unroll=True if rt.unroll else 1)
    return x, new_caches


def stack_paged(params: dict, x: jax.Array, ctx_len, block_table, n_valid,
                state_idx, cfg: ArchConfig, rt: Runtime, caches, *,
                fused: bool = False):
    """C-token step over the unified state-cache — prefill chunks and the
    decode window share this path, for every slot kind. x:
    (B, C, D); ctx_len/n_valid: (B,) int32; block_table: (B, max_pages)
    int32; state_idx: (B, 2) int32 — column 0 is each row's slab index
    (SSM state), column 1 its cross-region entry (encoder-output KV);
    out-of-range sentinels mark rows without that region. caches:
    per-slot region pytrees stacked over periods
    (``slot_init_paged_cache``). The scan carries them whole and each
    period reads and writes its own index in place: no period's region is
    sliced out, and no second copy is stacked. ``fused`` routes every
    attention through the ragged decode megakernel (the serving decode
    tick, speculative window included; prefill chunks of any length stay
    on the gather path). Returns
    (x, new_caches)."""
    def body(carry, xs):
        return _period_body(carry, xs, cfg=cfg, rt=rt, mode="paged",
                            paged_ctx=(ctx_len, block_table, n_valid,
                                       state_idx),
                            fused=fused)
    n_periods = jax.tree_util.tree_leaves(params["slots"])[0].shape[0]
    (x, _, new_caches), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), list(caches)),
        (tuple(params["slots"]), jnp.arange(n_periods, dtype=jnp.int32)),
        unroll=True if rt.unroll else 1)
    return x, new_caches


def _with_pattern(cfg: ArchConfig, pattern: tuple) -> ArchConfig:
    import dataclasses
    n_layers = cfg.n_enc_layers if cfg.enc_dec else cfg.n_layers
    return dataclasses.replace(cfg, pattern=pattern, n_layers=n_layers)


# ---------------------------------------------------------------------------
# Cache allocation
# ---------------------------------------------------------------------------

def slot_init_cache(slot: str, cfg: ArchConfig, batch: int, max_seq: int,
                    dtype=jnp.bfloat16, n_periods: int | None = None,
                    kv_quant: bool = False):
    """Zero cache for one slot, stacked over periods (leading P dim).
    kv_quant: store attention K/V as codebook codes (uint8) + per-position
    scale. The level set is NOT fixed here — codes are interpreted under
    ``Runtime.kv_scheme`` at quantize/attend time (``uniform8`` = the plain
    int8 baseline, ``sp2_8``/``spx_8_x3`` = non-uniform SPx), so the cache
    layout is scheme-independent: 1 byte/element + 4 bytes/position."""
    mixer, _ = _parse_slot(slot)
    P = n_periods if n_periods is not None else cfg.n_periods

    def stackP(tree):
        return jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a, (P,) + a.shape).copy(), tree)

    if mixer in ("attn", "xdec"):
        if kv_quant:
            def qkv():
                return {"codes": jnp.zeros((P, batch, cfg.n_kv_heads,
                                            max_seq, cfg.dh), jnp.uint8),
                        "scale": jnp.ones((P, batch, cfg.n_kv_heads,
                                           max_seq, 1), jnp.float32)}
            cache = {"k": qkv(), "v": qkv()}
        else:
            kv = jnp.zeros((P, batch, cfg.n_kv_heads, max_seq, cfg.dh),
                           dtype)
            cache = {"k": kv, "v": kv + 0}
        if mixer == "xdec":
            xkv = jnp.zeros((P, batch, cfg.n_kv_heads, cfg.enc_seq_len,
                             cfg.dh), dtype)
            cache["xk"] = xkv
            cache["xv"] = xkv + 0
        return cache
    if mixer == "mamba":
        di = cfg.ssm_expand * cfg.d_model
        base = {"h": jnp.zeros((batch, di, cfg.ssm_d_state), jnp.float32),
                "conv": jnp.zeros((batch, cfg.ssm_d_conv - 1, di), dtype)}
        return stackP(base)
    if mixer == "mlstm":
        di = cfg.ssm_expand * cfg.d_model
        nh = cfg.lstm_heads
        dh = di // nh
        base = {"C": jnp.zeros((batch, nh, dh, dh), jnp.float32),
                "n": jnp.zeros((batch, nh, dh), jnp.float32),
                "m": jnp.zeros((batch, nh), jnp.float32),
                "conv": jnp.zeros((batch, cfg.ssm_d_conv - 1, di), dtype)}
        return stackP(base)
    if mixer == "slstm":
        nh = cfg.lstm_heads
        dh = cfg.d_model // nh
        base = {k: jnp.zeros((batch, nh, dh), jnp.float32)
                for k in ("c", "n", "m", "h")}
        return stackP(base)
    raise ValueError(slot)


def slot_init_paged_cache(slot: str, cfg: ArchConfig, n_pages: int,
                          page_size: int, dtype=jnp.bfloat16,
                          n_periods: int | None = None,
                          kv_quant: bool = False, n_slabs: int = 0,
                          n_cross: int = 0):
    """Device arrays for one slot's state-cache region, stacked over
    periods (axis 0) with the shared pool axis at axis 1:

      * attn: token-paged K/V pools {"kp", "vp"} each
        (P, n_pages, Hkv, page_size, dh) — or, when ``kv_quant``, each a
        {"codes" uint8, "scale" f32 (P, n_pages, Hkv, lanes)} dict (codes
        interpreted under ``Runtime.kv_scheme``; the scale of a page's
        token t at lane t, ``spx.kv_scale_lanes``; ``dtype`` is ignored
        for them)
      * xdec: the same self-attention pools plus the read-only cross
        region {"xk", "xv"} each (P, n_cross, Hkv, enc_seq_len, dh) —
        one entry per *distinct input*, shared across sequences
      * mamba / mlstm / slstm: the slab region — per-sequence recurrent
        state leaves shaped (P, n_slabs, ...); scan/cell states are f32,
        conv windows use ``dtype``

    Every region is shared by every sequence — ownership lives in the
    host-side StateCache (serving/kv_cache.py); the device only ever sees
    block tables and (slab, cross) index columns."""
    mixer, _ = _parse_slot(slot)
    P = n_periods if n_periods is not None else cfg.n_periods
    if mixer in ("attn", "xdec"):
        if kv_quant:
            def pool():
                return {"codes": jnp.zeros((P, n_pages, cfg.n_kv_heads,
                                            page_size, cfg.dh), jnp.uint8),
                        "scale": jnp.ones((P, n_pages, cfg.n_kv_heads,
                                           kv_scale_lanes(page_size)),
                                          jnp.float32)}
            cache = {"kp": pool(), "vp": pool()}
        else:
            kp = jnp.zeros((P, n_pages, cfg.n_kv_heads, page_size, cfg.dh),
                           dtype)
            cache = {"kp": kp, "vp": kp + 0}
        if mixer == "xdec":
            xkv = jnp.zeros((P, n_cross, cfg.n_kv_heads, cfg.enc_seq_len,
                             cfg.dh), dtype)
            cache["xk"] = xkv
            cache["xv"] = xkv + 0
        return cache
    if mixer == "mamba":
        di = cfg.ssm_expand * cfg.d_model
        return {"h": jnp.zeros((P, n_slabs, di, cfg.ssm_d_state),
                               jnp.float32),
                "conv": jnp.zeros((P, n_slabs, cfg.ssm_d_conv - 1, di),
                                  dtype)}
    if mixer == "mlstm":
        di = cfg.ssm_expand * cfg.d_model
        nh = cfg.lstm_heads
        dh = di // nh
        return {"C": jnp.zeros((P, n_slabs, nh, dh, dh), jnp.float32),
                "n": jnp.zeros((P, n_slabs, nh, dh), jnp.float32),
                "m": jnp.zeros((P, n_slabs, nh), jnp.float32),
                "conv": jnp.zeros((P, n_slabs, cfg.ssm_d_conv - 1, di),
                                  dtype)}
    if mixer == "slstm":
        nh = cfg.lstm_heads
        dh = cfg.d_model // nh
        return {k: jnp.zeros((P, n_slabs, nh, dh), jnp.float32)
                for k in ("c", "n", "m", "h")}
    raise ValueError(slot)
