"""Encoder-decoder backbone (Whisper-small assignment). The audio conv
frontend is a stub per the assignment: inputs are precomputed frame
embeddings (B, enc_seq_len, D). The decoder is an ``xdec+dense`` stack with
per-layer cross-attention KV cached at prefill."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.nn.layers import (dense_init, embedding_apply, embedding_init,
                             norm_apply, norm_init)
from repro.runtime import Runtime
from repro.nn.transformer import (_cross_kv, slot_init_cache,
                                  slot_init_paged_cache, stack_apply,
                                  stack_decode, stack_init, stack_paged,
                                  stack_prefill)
from .lm import _default_positions, _head_w, chunked_ce

__all__ = ["encdec_init", "encdec_loss", "encdec_encode", "encdec_prefill",
           "encdec_decode_step", "encdec_init_caches", "enc_cfg", "dec_cfg",
           "encdec_paged_init_caches", "encdec_cross_kv",
           "encdec_paged_step", "encdec_paged_fused_step"]


def enc_cfg(cfg: ArchConfig) -> ArchConfig:
    return dataclasses.replace(cfg, pattern=("attn+dense",),
                               n_layers=cfg.n_enc_layers)


def dec_cfg(cfg: ArchConfig) -> ArchConfig:
    return dataclasses.replace(cfg, pattern=("xdec+dense",))


def encdec_init(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 6)
    return {
        "embed": embedding_init(ks[0], cfg.vocab_size, cfg.d_model,
                                dtype=dtype),
        "enc_stack": stack_init(ks[1], enc_cfg(cfg), dtype=dtype),
        "enc_norm": norm_init(cfg.norm, cfg.d_model, dtype),
        "dec_stack": stack_init(ks[2], dec_cfg(cfg), dtype=dtype),
        "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
        "head": dense_init(ks[3], cfg.d_model, cfg.vocab_size, dtype=dtype),
    }


def encdec_encode(params, frames: jax.Array, cfg: ArchConfig, rt: Runtime):
    """frames: (B, S_enc, D) stub embeddings -> encoder output."""
    b, s, _ = frames.shape
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    h, _ = stack_apply(params["enc_stack"], frames, pos, enc_cfg(cfg), rt,
                       causal=False)
    return norm_apply(cfg.norm, params["enc_norm"], h)


def encdec_loss(params, batch: dict, cfg: ArchConfig, rt: Runtime):
    """batch: {'frames': (B,S_enc,D), 'tokens': (B,S), 'labels': (B,S)}."""
    enc_out = encdec_encode(params, batch["frames"], cfg, rt)
    tokens = batch["tokens"]
    b, s = tokens.shape
    pos = _default_positions(cfg, b, s)
    x = embedding_apply(params["embed"], tokens)
    h, aux = stack_apply(params["dec_stack"], x, pos, dec_cfg(cfg), rt,
                         enc_out=enc_out)
    h = norm_apply(cfg.norm, params["final_norm"], h)
    ce, z = chunked_ce(h, params["head"]["w"], batch["labels"], rt=rt,
                       unroll=rt.unroll)
    return ce + 1e-4 * z, {"ce": ce, "z": z}


def encdec_init_caches(cfg: ArchConfig, batch: int, max_seq: int,
                       dtype=jnp.bfloat16, kv_quant: bool = False):
    dcfg = dec_cfg(cfg)
    return [slot_init_cache(slot, dcfg, batch, max_seq, dtype,
                            kv_quant=kv_quant)
            for slot in dcfg.pattern]


def encdec_prefill(params, frames, tokens, caches, cfg: ArchConfig,
                   rt: Runtime):
    """Encode + run decoder prompt, filling self- and cross-attn caches."""
    enc_out = encdec_encode(params, frames, cfg, rt)
    b, s = tokens.shape
    pos = _default_positions(cfg, b, s)
    x = embedding_apply(params["embed"], tokens)
    dcfg = dec_cfg(cfg)
    h, new_caches, _ = stack_prefill(params["dec_stack"], x, pos, dcfg, rt,
                                     caches, enc_out=enc_out)
    h = norm_apply(cfg.norm, params["final_norm"], h[:, -1:])
    logits = jnp.dot(h[:, 0], params["head"]["w"].astype(h.dtype))
    return logits, new_caches


def encdec_decode_step(params, token, pos, caches, cfg: ArchConfig,
                       rt: Runtime):
    x = embedding_apply(params["embed"], token[:, None])
    dcfg = dec_cfg(cfg)
    h, new_caches = stack_decode(params["dec_stack"], x, pos, dcfg, rt,
                                 caches)
    h = norm_apply(cfg.norm, params["final_norm"], h)
    logits = jnp.dot(h[:, 0], params["head"]["w"].astype(h.dtype))
    return logits, new_caches


# -- paged serving (unified state-cache) -------------------------------------

def encdec_paged_init_caches(cfg: ArchConfig, n_pages: int, page_size: int,
                             dtype=jnp.bfloat16, kv_quant: bool = False,
                             n_slabs: int = 0, n_cross: int = 0):
    """Decoder state-cache regions: token-paged self-attention KV pools
    plus ``n_cross`` read-only encoder-output entries per xdec slot (the
    encoder itself holds no serving state — its output is projected once
    per distinct input via ``encdec_cross_kv`` and shared)."""
    dcfg = dec_cfg(cfg)
    return [slot_init_paged_cache(slot, dcfg, n_pages, page_size, dtype,
                                  kv_quant=kv_quant, n_slabs=n_slabs,
                                  n_cross=n_cross)
            for slot in dcfg.pattern]


def encdec_cross_kv(params, frames: jax.Array, cfg: ArchConfig,
                    rt: Runtime):
    """Run the encoder once and project its output through every decoder
    slot x period's cross-attention K/V: frames (B, S_enc, D) -> per-slot
    list of ``None`` (non-xdec slots) or {"xk", "xv"} arrays shaped
    (P, B, Hkv, S_enc, dh) — exactly what ``lm.paged_fill_cross`` writes
    into a cross entry (B = 1 there: one entry per distinct input). The
    per-period projection weights are stacked on axis 0, so a vmap over
    the slot params applies all periods in one call (QuantizedTensor is a
    registered pytree — vmap slices its codes like any array)."""
    enc_out = encdec_encode(params, frames, cfg, rt)
    dcfg = dec_cfg(cfg)
    out = []
    for j, slot in enumerate(dcfg.pattern):
        if slot.split("+")[0] != "xdec":
            out.append(None)
            continue
        slot_params = params["dec_stack"]["slots"][j]

        def per_period(p_x):
            k, v = _cross_kv(p_x, enc_out, dcfg.n_kv_heads, dcfg.dh, rt)
            # (B, S_enc, Hkv, dh) -> (B, Hkv, S_enc, dh), the cache layout
            return (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))

        xk, xv = jax.vmap(per_period)(slot_params["xattn"])
        out.append({"xk": xk, "xv": xv})
    return out


def encdec_paged_step(params, tokens, ctx_len, block_table, n_valid,
                      state_idx, caches, cfg: ArchConfig, rt: Runtime):
    """Decoder twin of ``lm.lm_paged_step`` — same contract, decoder
    pattern, cross-attention reading the shared cross region via
    ``state_idx[:, 1]``. Returns (logits (B, V) at each row's last valid
    position, new_caches)."""
    x = embedding_apply(params["embed"], tokens)
    dcfg = dec_cfg(cfg)
    h, new_caches = stack_paged(params["dec_stack"], x, ctx_len,
                                block_table, n_valid, state_idx, dcfg, rt,
                                caches)
    h = norm_apply(cfg.norm, params["final_norm"], h)
    last = jnp.clip(n_valid - 1, 0, tokens.shape[1] - 1)          # (B,)
    h_last = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
    logits = jnp.dot(h_last, params["head"]["w"].astype(h.dtype))
    return logits, new_caches


def encdec_paged_fused_step(params, tokens, ctx_len, block_table, n_valid,
                            state_idx, caches, cfg: ArchConfig,
                            rt: Runtime):
    """Decoder twin of ``lm.lm_paged_fused_step``: the self-attention
    rides the ragged decode megakernel; cross-attention stays on the
    gather path (its KV is a dense per-entry block, not pages)."""
    x = embedding_apply(params["embed"], tokens)
    dcfg = dec_cfg(cfg)
    h, new_caches = stack_paged(params["dec_stack"], x, ctx_len,
                                block_table, n_valid, state_idx, dcfg, rt,
                                caches, fused=True)
    h = norm_apply(cfg.norm, params["final_norm"], h)
    logits = jnp.dot(h, params["head"]["w"].astype(h.dtype))
    return logits, new_caches
