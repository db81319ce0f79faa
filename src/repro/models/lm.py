"""Decoder-only language model: init / train loss / prefill / decode.

Covers dense (granite, gemma, qwen2.5, minitron), MoE (kimi-k2, olmoe),
SSM (xlstm), hybrid (jamba) and VLM-backbone (qwen2-vl, M-RoPE) families —
everything except enc-dec (see encdec.py). The vocabulary head is evaluated
in sequence chunks (never materializing (B, S, V)); the head weight shards
over the model axis when the vocab divides it.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.nn.layers import (dense_apply, dense_init, embedding_apply,
                             embedding_init, norm_apply, norm_init)
from repro.runtime import Runtime
from repro.nn.transformer import (slot_init_cache, slot_init_paged_cache,
                                  stack_apply, stack_decode, stack_paged,
                                  stack_prefill, stack_init)

__all__ = ["lm_init", "lm_loss", "lm_logits", "lm_prefill", "lm_decode_step",
           "init_caches", "paged_init_caches", "lm_paged_step",
           "lm_paged_fused_step", "paged_copy_page",
           "paged_gather_pages", "paged_scatter_pages",
           "paged_gather_slabs", "paged_scatter_slabs", "paged_reset_slabs",
           "paged_fill_cross", "chunked_ce"]

LOSS_CHUNK = 256
AUX_WEIGHT = 0.01
Z_WEIGHT = 1e-4


def lm_init(key, cfg: ArchConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "embed": embedding_init(ks[0], cfg.vocab_size, cfg.d_model,
                                dtype=dtype),
        "stack": stack_init(ks[1], cfg, dtype=dtype),
        "final_norm": norm_init(cfg.norm, cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["head"] = dense_init(ks[2], cfg.d_model, cfg.vocab_size,
                               dtype=dtype)
    return p


def _head_w(params: dict, cfg: ArchConfig):
    if cfg.tie_embeddings:
        return params["embed"]["table"].T          # (D, V)
    return params["head"]["w"]


def _serving_precision(cfg: ArchConfig):
    """The matmul precision the serving steps trace under: every dot that
    states none, inside the Pallas kernels too, takes
    ``cfg.matmul_precision``."""
    return jax.default_matmul_precision(cfg.matmul_precision)


def _default_positions(cfg: ArchConfig, b: int, s: int):
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    if cfg.mrope_sections is not None:
        return jnp.broadcast_to(pos[:, None, :], (b, 3, s))
    return pos


def chunked_ce(h: jax.Array, w_head: jax.Array, labels: jax.Array, *,
               chunk: int = LOSS_CHUNK, rt: Runtime | None = None,
               unroll: bool = False):
    """Mean token cross-entropy, scanning over sequence chunks so the
    (B, chunk, V) logits block is the only vocab-sized live tensor.
    Also returns z-loss (log^2 Z) for stability."""
    b, s, d = h.shape
    v = w_head.shape[-1]
    chunk = min(chunk, s)
    if s % chunk:
        chunk = s
    n_chunks = s // chunk
    w32 = w_head.astype(jnp.bfloat16)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, i):
        # checkpointed: the (B, chunk, V) logits block is recomputed in the
        # backward instead of being saved once per chunk (one cheap matmul)
        ce_sum, z_sum = carry
        h_i = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, axis=1)
        y_i = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        logits = jnp.dot(h_i.astype(jnp.bfloat16), w32,
                         preferred_element_type=jnp.float32)
        if rt is not None and rt.mesh is not None \
                and rt.model_axis is not None \
                and v % rt.mesh.shape[rt.model_axis] == 0:
            from jax.sharding import NamedSharding
            dp = rt.data_axes if rt.data_axes else None
            logits = jax.lax.with_sharding_constraint(
                logits, NamedSharding(rt.mesh, P(dp, None, rt.model_axis)))
        lse = jax.nn.logsumexp(logits, axis=-1)
        true = jnp.take_along_axis(logits, y_i[..., None],
                                   axis=-1)[..., 0]
        ce_sum = ce_sum + jnp.sum(lse - true)
        z_sum = z_sum + jnp.sum(lse * lse)
        return (ce_sum, z_sum), None

    if n_chunks == 1:
        (ce, z), _ = body((jnp.zeros((), jnp.float32),
                           jnp.zeros((), jnp.float32)), 0)
    else:
        (ce, z), _ = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
            jnp.arange(n_chunks), unroll=True if unroll else 1)
    n_tok = b * s
    return ce / n_tok, z / n_tok


def _backbone(params, cfg: ArchConfig, tokens, positions, rt: Runtime,
              embeds=None):
    x = embeds if embeds is not None else embedding_apply(params["embed"],
                                                          tokens)
    # sequence-sharded from the embedding on (SP/CP); batch over data axes
    from repro.nn.transformer import _sp_constrain
    x = _sp_constrain(x, rt)
    h, aux = stack_apply(params["stack"], x, positions, cfg, rt)
    return norm_apply(cfg.norm, params["final_norm"], h), aux


def lm_loss(params, batch: dict, cfg: ArchConfig, rt: Runtime):
    """batch: {'tokens': (B,S) int32, 'labels': (B,S) int32,
    optional 'positions'}. Returns (scalar loss, metrics dict)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    positions = batch.get("positions")
    if positions is None:
        positions = _default_positions(cfg, b, s)
    h, aux = _backbone(params, cfg, tokens, positions, rt,
                       embeds=batch.get("embeds"))
    ce, z = chunked_ce(h, _head_w(params, cfg), batch["labels"], rt=rt,
                       unroll=rt.unroll)
    loss = ce + AUX_WEIGHT * aux + Z_WEIGHT * z
    return loss, {"ce": ce, "aux": aux, "z": z}


def lm_logits(params, tokens, cfg: ArchConfig, rt: Runtime, positions=None,
              embeds=None):
    """Full-sequence logits (small-model/test use only)."""
    b, s = tokens.shape
    if positions is None:
        positions = _default_positions(cfg, b, s)
    h, _ = _backbone(params, cfg, tokens, positions, rt, embeds=embeds)
    return jnp.dot(h, _head_w(params, cfg).astype(h.dtype))


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------

def init_caches(cfg: ArchConfig, batch: int, max_seq: int,
                dtype=jnp.bfloat16, kv_quant: bool = False):
    return [slot_init_cache(slot, cfg, batch, max_seq, dtype,
                            kv_quant=kv_quant)
            for slot in cfg.pattern]


def lm_prefill(params, tokens, caches, cfg: ArchConfig, rt: Runtime,
               positions=None, embeds=None):
    """Run the prompt through the stack, fill caches, return last-position
    logits and the caches."""
    b, s = tokens.shape[:2]
    if positions is None:
        positions = _default_positions(cfg, b, s)
    x = embeds if embeds is not None else embedding_apply(params["embed"],
                                                          tokens)
    from repro.nn.transformer import _sp_constrain
    x = _sp_constrain(x, rt)
    with _serving_precision(cfg):
        h, new_caches, _ = stack_prefill(params["stack"], x, positions, cfg,
                                         rt, caches)
        h = norm_apply(cfg.norm, params["final_norm"], h[:, -1:])
        logits = jnp.dot(h[:, 0], _head_w(params, cfg).astype(h.dtype))
    return logits, new_caches


def lm_decode_step(params, token, pos, caches, cfg: ArchConfig, rt: Runtime):
    """One decode step. token: (B,) int32; pos: () int32 (current write
    position = number of tokens already in cache). Returns (logits (B, V),
    new_caches)."""
    x = embedding_apply(params["embed"], token[:, None])
    with _serving_precision(cfg):
        h, new_caches = stack_decode(params["stack"], x, pos, cfg, rt,
                                     caches)
        h = norm_apply(cfg.norm, params["final_norm"], h)
        logits = jnp.dot(h[:, 0], _head_w(params, cfg).astype(h.dtype))
    return logits, new_caches


# -- paged serving (docs/SERVING.md) ----------------------------------------

def paged_init_caches(cfg: ArchConfig, n_pages: int, page_size: int,
                      dtype=jnp.bfloat16, kv_quant: bool = False,
                      n_slabs: int = 0, n_cross: int = 0):
    """Device state-cache regions for every slot in the pattern: KV page
    pools for attention slots, ``n_slabs`` recurrent-state slabs for SSM
    slots, ``n_cross`` read-only encoder-output entries for xdec slots —
    heterogeneous (hybrid) patterns get exactly the regions each slot
    needs. ``kv_quant`` switches the page pools to the codes+scale
    quantized layout (scheme from ``Runtime.kv_scheme`` at step time)."""
    return [slot_init_paged_cache(slot, cfg, n_pages, page_size, dtype,
                                  kv_quant=kv_quant, n_slabs=n_slabs,
                                  n_cross=n_cross)
            for slot in cfg.pattern]


# region partitioning by leaf key: the page pools ("kp"/"vp" — arrays or
# codes+scale dicts), the read-only cross entries ("xk"/"xv"), and
# everything else is per-sequence slab state (SSM h/conv/C/n/m/c leaves)
_PAGE_KEYS = ("kp", "vp")
_CROSS_KEYS = ("xk", "xv")


def _slab_keys(slot_cache: dict):
    return [k for k in slot_cache
            if k not in _PAGE_KEYS and k not in _CROSS_KEYS]


def paged_copy_page(caches, src, dst):
    """Copy one physical KV page ``src`` -> ``dst`` across every layer,
    period and head (K and V — and codes+scale pairs when the pool is
    quantized). This is the serving engine's copy-on-write: a request
    whose prompt fully matches a shared page up to its last token gets a
    private copy to finish (and later decode into) so the shared original
    stays immutable. Page index is axis 1 of every page-region leaf
    (``(P, n_pages, Hkv, page_size, dh)``); ``src``/``dst`` may be traced
    scalars, so one jit of this function serves every (src, dst) pair.
    Slab and cross regions pass through untouched — pages are the only
    copy-on-write region (slabs are exclusive, cross entries immutable).
    """
    def cp(leaf):
        return leaf.at[:, dst].set(leaf[:, src])
    out = []
    for slot_cache in caches:
        new = dict(slot_cache)
        for k in _PAGE_KEYS:
            if k in slot_cache:
                new[k] = jax.tree_util.tree_map(cp, slot_cache[k])
        out.append(new)
    return out


def paged_gather_pages(caches, pages):
    """Gather whole physical KV pages across every page-region leaf: the
    serving engine's preemption snapshot. ``pages`` is a (n,) int32 page
    index vector; each ``(P, n_pages, Hkv, page_size, dh)`` leaf yields
    ``(P, n, Hkv, page_size, dh)``. The index vector is traced, so one
    jit per padded length serves every page set of that size (the engine
    pads to powers of two, duplicating the last page — callers slice the
    duplicates off host-side). Returns the page-region subtree only (one
    dict per slot; empty for slab-only slots) — slab state snapshots
    through ``paged_gather_slabs``."""
    return [{k: jax.tree_util.tree_map(lambda leaf: leaf[:, pages],
                                       slot_cache[k])
             for k in _PAGE_KEYS if k in slot_cache}
            for slot_cache in caches]


def paged_scatter_pages(caches, pages, payload):
    """Scatter snapshotted pages back into the pool: the inverse of
    ``paged_gather_pages``, used when a preempted sequence resumes into
    freshly allocated pages. Duplicate indices in ``pages`` (the engine's
    pow2 padding) carry identical payload rows, so the write is
    deterministic regardless of scatter order."""
    out = []
    for slot_cache, pay in zip(caches, payload):
        new = dict(slot_cache)
        for k in _PAGE_KEYS:
            if k in slot_cache:
                new[k] = jax.tree_util.tree_map(
                    lambda leaf, p: leaf.at[:, pages].set(p),
                    slot_cache[k], pay[k])
        out.append(new)
    return out


def paged_gather_slabs(caches, slab):
    """Snapshot one slab's recurrent state across every SSM slot: each
    ``(P, n_slabs, ...)`` slab leaf yields ``(P, ...)``. ``slab`` may be
    a traced scalar — one jit serves every slab index. Returns the
    slab-region subtree only (one dict per slot; empty for attention
    slots)."""
    return [{k: slot_cache[k][:, slab] for k in _slab_keys(slot_cache)}
            for slot_cache in caches]


def paged_scatter_slabs(caches, slab, payload):
    """Restore a snapshotted slab (inverse of ``paged_gather_slabs``) —
    the resumed sequence may land on a different slab index than it was
    preempted from; the pool's ``seq_slab`` says where."""
    out = []
    for slot_cache, pay in zip(caches, payload):
        new = dict(slot_cache)
        for k in _slab_keys(slot_cache):
            new[k] = slot_cache[k].at[:, slab].set(
                pay[k].astype(slot_cache[k].dtype))
        out.append(new)
    return out


def paged_reset_slabs(caches, slab):
    """Zero one slab across every SSM slot — a freshly admitted sequence
    must start from the zero recurrent state, and its slab still holds
    whatever the previous owner left behind (pages don't need this: every
    page position is written before it is attended)."""
    out = []
    for slot_cache in caches:
        new = dict(slot_cache)
        for k in _slab_keys(slot_cache):
            leaf = slot_cache[k]
            new[k] = leaf.at[:, slab].set(
                jnp.zeros(leaf.shape[:1] + leaf.shape[2:], leaf.dtype))
        out.append(new)
    return out


def paged_fill_cross(caches, idx, entries):
    """Write one encoder pass's projected K/V into cross entry ``idx``
    across every xdec slot. ``entries``: per-slot ``None`` (non-xdec) or
    {"xk", "xv"} arrays shaped (P, 1, Hkv, enc_seq_len, dh) — the output
    of ``models.encdec.encdec_cross_kv`` on a single input. Entries are
    written once here and only ever read by the decode path (read-only
    sharing across sequences)."""
    out = []
    for slot_cache, ent in zip(caches, entries):
        new = dict(slot_cache)
        if ent is not None:
            for k in _CROSS_KEYS:
                new[k] = slot_cache[k].at[:, idx].set(
                    ent[k][:, 0].astype(slot_cache[k].dtype))
        out.append(new)
    return out


def lm_paged_step(params, tokens, ctx_len, block_table, n_valid, state_idx,
                  caches, cfg: ArchConfig, rt: Runtime):
    """One paged prefill step: run the next C tokens of each sequence's
    prompt (a chunk; C may be 1) against the unified state-cache.

    tokens: (B, C) int32 (rows may be padded past ``n_valid``);
    ctx_len: (B,) int32 tokens already in the pages; block_table:
    (B, max_pages) int32; n_valid: (B,) int32 valid tokens in this chunk
    (0 = inactive row); state_idx: (B, 2) int32 per-row (slab, cross)
    indices, out-of-range sentinels for rows without that region (pure
    attention patterns pass all-sentinel). Returns (logits (B, V) at each
    row's last valid position, new_caches).
    """
    with _serving_precision(cfg), jax.named_scope("prefill_step"):
        x = embedding_apply(params["embed"], tokens)
        h, new_caches = stack_paged(params["stack"], x, ctx_len,
                                    block_table, n_valid, state_idx, cfg,
                                    rt, caches)
        with jax.named_scope("head"):
            h = norm_apply(cfg.norm, params["final_norm"], h)
            last = jnp.clip(n_valid - 1, 0, tokens.shape[1] - 1)  # (B,)
            h_last = jnp.take_along_axis(h, last[:, None, None],
                                         axis=1)[:, 0]
            logits = jnp.dot(h_last, _head_w(params, cfg).astype(h.dtype))
    return logits, new_caches


def lm_paged_fused_step(params, tokens, ctx_len, block_table, n_valid,
                        state_idx, caches, cfg: ArchConfig, rt: Runtime):
    """One decode tick: plain decode (C == 1) *and* the speculative
    verify window (C == K+1) through the ragged decode megakernel — every
    layer's attention is ONE ``paged_decode_ragged`` launch over the
    batch's ragged (slot, attend_len) grid.

    Arguments as ``lm_paged_step``: ``tokens`` (B, C) is each row's next
    window (the pending token plus up to C-1 drafts, padded past
    ``n_valid``), written to the pages and attended causally within the
    window. Logits come back at every window position, (B, C, V) —
    position j is the distribution for the token after window token j,
    which is what acceptance compares draft j+1 against. Rows past
    ``n_valid`` carry garbage logits (the kernel returns zeros for them
    pre-head) — the engine only reads positions < n_valid.
    """
    with _serving_precision(cfg), jax.named_scope("decode_step"):
        x = embedding_apply(params["embed"], tokens)
        h, new_caches = stack_paged(params["stack"], x, ctx_len,
                                    block_table, n_valid, state_idx, cfg,
                                    rt, caches, fused=True)
        with jax.named_scope("head"):
            h = norm_apply(cfg.norm, params["final_norm"], h)
            logits = jnp.dot(h, _head_w(params, cfg).astype(h.dtype))
    return logits, new_caches
