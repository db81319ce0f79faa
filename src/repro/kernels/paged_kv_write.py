"""Pallas TPU kernel: write a step's new K/V rows into the stacked page
pools in place.

The serving steps carry every layer's pools as one stacked array through
the layer scan. This kernel aliases those pools (``input_output_aliases``)
and rewrites only the pages the step's rows land in, at ``[layer, page]``:
no layer is sliced out, and no second pool is made.

A row is not stored alone. A uint8 pool's tile interleaves four tokens in
each 32-bit word, so one token's codes cannot be written by themselves;
the kernel streams each touched page through VMEM instead and merges the
new rows into it. Grid: one step per touched page. The wrapper compacts
the touched pages to the front and repeats the last one over the rest of
the grid; a repeated block index is neither fetched nor written back
again, and merging the same rows twice gives the same page. Each step
receives the page of every pool (block ``[layer, page]``), an image of
the page that holds the new rows at their offsets, and the page-local
token range ``[lo, hi)`` they cover, and writes
``where(lo <= token < hi, image, page)``.

Tokens sit on axis 1 of a page block: the sublanes of a
``(P, n_pages, Hkv, page_size, w)`` leaf, the lanes of a
``(P, n_pages, Hkv, lanes)`` leaf (the SPx per-token scales).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["paged_kv_write_pallas"]


def _touched_pages(block_table, ctx_len, n_valid, page_size: int, c: int):
    """One entry per (slot, t) for the t-th page a slot's C-row window can
    reach: ``(page, lo, hi, base)``, each (B, T). ``[lo, hi)`` is the
    page-local token range the slot's rows ``ctx_len .. ctx_len + n_valid
    - 1`` cover (empty where the page is not touched) and ``base`` the
    window row at the page's token 0 (negative where the window starts
    inside the page). T is the most pages C consecutive tokens span."""
    n_t = (c + page_size - 2) // page_size + 1
    lp = ctx_len[:, None] // page_size + jnp.arange(n_t, dtype=jnp.int32)
    page = jnp.take_along_axis(
        block_table, jnp.clip(lp, 0, block_table.shape[1] - 1), axis=1)
    start = lp * page_size
    lo = jnp.clip(ctx_len[:, None] - start, 0, page_size)
    hi = jnp.clip((ctx_len + n_valid)[:, None] - start, 0, page_size)
    return page, lo, hi, start - ctx_len[:, None]


def _kv_write_kernel(layer_ref, page_ref, lo_ref, hi_ref, *refs):
    del layer_ref, page_ref           # consumed by the BlockSpec index maps
    n = len(refs) // 3
    images, pages, outs = refs[:n], refs[n:2 * n], refs[2 * n:]
    g = pl.program_id(0)
    lo, hi = lo_ref[g], hi_ref[g]
    for image, page, out in zip(images, pages, outs):
        tok = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
        out[...] = jnp.where((tok >= lo) & (tok < hi), image[...], page[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_kv_write_pallas(pools, rows, layer, block_table, ctx_len, n_valid,
                          *, interpret: bool = False):
    """Write rows ``i < n_valid[b]`` of each slot's window at positions
    ``ctx_len[b] + i`` of ``pools[k][layer]``, in place.

    pools: stacked leaves, each ``(P, n_pages, Hkv, page_size, w)`` with
    rows ``(B, C, Hkv, w)``, or ``(P, n_pages, Hkv, lanes)`` (one value a
    token, at lane ``offset``) with rows ``(B, C, Hkv)``; the first leaf
    is of the first kind. layer: () int32; block_table: (B, max_pages)
    int32; ctx_len, n_valid: (B,) int32. Pages written must belong to one
    slot each. Returns the pools."""
    page_size = pools[0].shape[3]
    n_pages = pools[0].shape[1]
    b, c = rows[0].shape[:2]
    page, lo, hi, base = (a.reshape(-1) for a in _touched_pages(
        block_table, ctx_len, n_valid, page_size, c))
    n_steps = page.shape[0]
    touched = hi > lo
    order = jnp.argsort(~touched, stable=True)
    last = jnp.maximum(jnp.sum(touched) - 1, 0)
    sel = order[jnp.minimum(jnp.arange(n_steps), last)]
    slot = sel // (n_steps // b)

    def image(pool, new):
        # (G, width) window rows at each token of the page, clipped: the
        # rows outside [lo, hi) are never stored
        width = pool.shape[3]
        src = jnp.clip(base[sel][:, None] + jnp.arange(width), 0, c - 1)
        return jnp.moveaxis(new[slot[:, None], src], 1, 2).astype(pool.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,             # layer, page, lo, hi
        grid=(n_steps,),
        in_specs=(
            [pl.BlockSpec((None,) + a.shape[2:],
                          lambda g, l, pg, lo, hi, nd=a.ndim:
                          (g,) + (0,) * (nd - 2)) for a in pools]
            + [pl.BlockSpec((None, None) + a.shape[2:],
                            lambda g, l, pg, lo, hi, nd=a.ndim:
                            (l[0], pg[g]) + (0,) * (nd - 2)) for a in pools]),
        out_specs=[pl.BlockSpec((None, None) + a.shape[2:],
                                lambda g, l, pg, lo, hi, nd=a.ndim:
                                (l[0], pg[g]) + (0,) * (nd - 2))
                   for a in pools])
    n = len(pools)
    return pl.pallas_call(
        _kv_write_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in pools],
        input_output_aliases={4 + n + k: k for k in range(n)},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      jnp.clip(page[sel], 0, n_pages - 1), lo[sel], hi[sel],
      *[image(p, r) for p, r in zip(pools, rows)], *pools)
