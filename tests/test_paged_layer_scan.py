"""The paged steps carry every layer's state through the layer scan and
update it in place (``nn.transformer.stack_paged``). Checked here against
a straight per-layer reference that slices each period's weights and
state out, runs that period alone and stacks the state back: logits and
every cache leaf must be bit-identical, over chunked prefill, decode, a
speculative window W = K + 1 and an inactive slot, on a qwen2-style
stack (f32 and SPx KV pages) and on a jamba-style hybrid period
(attention, Mamba slabs, MoE) repeated twice.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import lm as lm_mod
from repro.nn.attention import quantize_pages
from repro.nn.layers import embedding_apply, norm_apply
from repro.nn.transformer import stack_paged
from repro.runtime import Runtime

jax.config.update("jax_platform_name", "cpu")

B, PAGE, MAX_PAGES = 3, 8, 4
N_PAGES = B * MAX_PAGES + 1


def _per_layer_stack(params, x, ctx_len, block_table, n_valid, state_idx,
                     cfg, rt, caches, *, fused=False):
    """The scan written out: period l's weights and state sliced out, the
    period run alone, the state stacked back."""
    n_p = jax.tree_util.tree_leaves(params["slots"])[0].shape[0]
    new = []
    for l in range(n_p):
        one = jax.tree_util.tree_map(lambda a: a[l:l + 1], (params, caches))
        x, c = stack_paged(one[0], x, ctx_len, block_table, n_valid,
                           state_idx, cfg, rt, one[1], fused=fused)
        new.append(c)
    return x, jax.tree_util.tree_map(lambda *a: jnp.concatenate(a), *new)


def _reference(params, tokens, ctx_len, block_table, n_valid, state_idx,
               caches, cfg, rt, *, fused):
    """``lm_paged_step`` (last valid row's logits) or, with ``fused``,
    ``lm_paged_fused_step`` (every window row), over the per-layer
    stack."""
    with jax.default_matmul_precision(cfg.matmul_precision):
        x = embedding_apply(params["embed"], tokens)
        h, caches = _per_layer_stack(params["stack"], x, ctx_len,
                                     block_table, n_valid, state_idx, cfg,
                                     rt, caches, fused=fused)
        h = norm_apply(cfg.norm, params["final_norm"], h)
        if not fused:
            last = jnp.clip(n_valid - 1, 0, tokens.shape[1] - 1)
            h = jnp.take_along_axis(h, last[:, None, None], axis=1)[:, 0]
        return jnp.dot(h, lm_mod._head_w(params, cfg).astype(h.dtype)), \
            caches


def _config(arch):
    if arch == "jamba2-mini":
        # two hybrid periods, so the attention layer sits at index 0 and 1
        return dataclasses.replace(reduced(get_config(arch), n_layers=16),
                                   n_kv_heads=2)
    return dataclasses.replace(reduced(get_config(arch), n_layers=3),
                               n_kv_heads=2)


def _random_caches(cfg, kv_scheme, rng):
    """Pools and slabs filled with noise, so a stray read or write
    anywhere shows."""
    caches = lm_mod.paged_init_caches(cfg, N_PAGES, PAGE,
                                      dtype=jnp.float32,
                                      kv_quant=kv_scheme is not None,
                                      n_slabs=B)

    def noise(path, a):
        keys = {getattr(k, "key", None) for k in path}
        if keys & {"kp", "vp"} and kv_scheme is not None:
            return a               # replaced whole below
        return jnp.asarray(rng.standard_normal(a.shape), a.dtype)

    caches = jax.tree_util.tree_map_with_path(noise, caches)
    if kv_scheme is not None:
        for c in caches:
            for k in ("kp", "vp"):
                if k in c:
                    shape = c[k]["codes"].shape
                    c[k] = quantize_pages(jnp.asarray(
                        rng.standard_normal(shape), jnp.float32), kv_scheme)
    return caches


# (step, C, ctx_len, n_valid) per tick: a prefill chunk whose second slot
# crosses a page boundary, decode, a K+1 = 4 spec window with a ragged
# tail and a plain fused decode; slot 2 stays inactive throughout
TICKS = [("step", 8, [0, 5, 3], [8, 6, 0]),
         ("step", 1, [8, 11, 0], [1, 1, 0]),
         ("fused", 4, [9, 12, 0], [4, 2, 0]),
         ("fused", 1, [13, 14, 0], [1, 1, 0])]


@pytest.mark.parametrize("arch,kv_scheme", [("qwen2-vl-2b", None),
                                            ("qwen2-vl-2b", "spx_8_x3"),
                                            ("jamba2-mini", None)])
def test_paged_steps_bit_identical_to_per_layer_reference(arch, kv_scheme):
    cfg = _config(arch)
    rt = Runtime(impl="ref", q_chunk=16, kv_quant=kv_scheme is not None,
                 kv_scheme=kv_scheme or "uniform8")
    rng = np.random.default_rng(7)
    params = lm_mod.lm_init(jax.random.PRNGKey(1), cfg)
    caches = _random_caches(cfg, kv_scheme, rng)
    assert cfg.n_periods > 1
    block_table = jnp.asarray(rng.permutation(N_PAGES)[:B * MAX_PAGES]
                              .reshape(B, MAX_PAGES), jnp.int32)
    # slot 2 is inactive: no slab (the out-of-range sentinel)
    state_idx = jnp.asarray([[0, 0], [1, 0], [B, 0]], jnp.int32)
    steps = {"step": jax.jit(lm_mod.lm_paged_step, static_argnums=(7, 8)),
             "fused": jax.jit(lm_mod.lm_paged_fused_step,
                              static_argnums=(7, 8))}
    refs = {k: jax.jit(lambda *a, fused=(k == "fused"): _reference(
        *a, fused=fused), static_argnums=(7, 8)) for k in steps}
    got, want = caches, caches
    for kind, c, ctx, nv in TICKS:
        tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, c)),
                             jnp.int32)
        args = (tokens, jnp.asarray(ctx, jnp.int32), block_table,
                jnp.asarray(nv, jnp.int32), state_idx)
        logits, got = steps[kind](params, *args, got, cfg, rt)
        ref_logits, want = refs[kind](params, *args, want, cfg, rt)
        assert np.asarray(logits).tobytes() == \
            np.asarray(ref_logits).tobytes(), (kind, c)
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert np.asarray(a).tobytes() == np.asarray(b).tobytes(), \
                (kind, c)
    # the inactive slot's slab and every page no active slot owns are as
    # they started
    owned = set(np.asarray(block_table[:2]).ravel().tolist())
    spare = np.asarray([p for p in range(N_PAGES) if p not in owned])

    def untouched(path, a, b):
        keys = {getattr(k, "key", None) for k in path}
        idx = spare if keys & {"kp", "vp"} else np.asarray([2])
        assert np.asarray(a)[:, idx].tobytes() == \
            np.asarray(b)[:, idx].tobytes(), keys

    jax.tree_util.tree_map_with_path(untouched, got, caches)
