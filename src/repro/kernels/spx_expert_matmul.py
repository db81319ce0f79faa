"""Pallas TPU kernel: grouped SPx matmul over a stack of expert weights.

A Mixture-of-Experts layer hands each routed (token, expert) pair to one
expert. The caller sorts the pairs by expert and pads each expert's group
to whole tiles of ``bm`` rows, so every row tile belongs to one expert.
The kernel is ``spx_matmul``'s pipeline with the weight tile chosen per
row tile: the tile's expert rides in as a scalar-prefetch operand and the
code BlockSpec's index map reads it, so only the codes of experts that
hold rows are streamed, and they are decoded in VMEM exactly as the dense
kernel decodes them. No dequantized expert weight ever reaches HBM.

Grid: (row tiles, N/bn, K/bk), K innermost, an f32 accumulator tile in
VMEM scratch. A tile that holds no rows (the grid is sized for the worst
case, every expert's last tile partial) computes nothing, writes zeros,
and maps its inputs to the blocks of the step before it, so the pipeline
fetches nothing for it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.spx import int4_group

from .decode import lut_decode, lut_operand, unpack_int4_tile

__all__ = ["spx_expert_matmul_pallas"]


def _kernel(tile_expert_ref, tile_rows_ref, x_ref, codes_ref, scale_ref,
            lut_ref, o_ref, acc_ref, *, group: int | None, n_k: int,
            out_dtype):
    g, k_idx = pl.program_id(0), pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(tile_rows_ref[g] > 0)
    def _accumulate():
        if group is None:
            codes = codes_ref[0].astype(jnp.int32)
        else:
            codes = unpack_int4_tile(codes_ref[0], group)
        w = lut_decode(codes, lut_ref)
        x = x_ref[...]
        acc_ref[...] += jax.lax.dot_general(
            x, w.astype(x.dtype),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...] * scale_ref[0]).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("packed", "bm", "bn", "bk", "out_dtype", "interpret"))
def spx_expert_matmul_pallas(x, codes, scale, lut, tile_expert, tile_rows,
                             *, packed: bool, bm: int, bn: int, bk: int,
                             out_dtype=None, interpret: bool = False):
    """x: (G * bm, K) rows in G tiles of ``bm``; tile g multiplies
    dequant(codes[tile_expert[g]]) (codes: (E, K, N), or (E, K, N // 2)
    int4-packed; scale: (E, 1, N)). ``tile_rows[g]``: rows of tile g that
    carry a pair (0 for an empty tile, whose output rows are zero). Rows
    past ``tile_rows[g]`` inside a tile are computed like the others; the
    caller keeps them zero. An empty tile's ``tile_expert`` should repeat
    the tile before it, so that its (unused) blocks need no new fetch.
    Returns (G * bm, N)."""
    m, k = x.shape
    n = scale.shape[-1]
    out_dtype = out_dtype or x.dtype
    bn, bk = min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn,
                                                         bk)
    n_g, n_j, n_k = m // bm, n // bn, k // bk
    pack_div = 2 if packed else 1
    group = int4_group(n) if packed else None
    assert group is None or bn % (2 * group) == 0, (n, bn, group)

    # an empty tile stays on the last block of the step before it
    def x_map(g, j, kk, te, tr):
        return (g, jnp.where(tr[g] > 0, kk, n_k - 1))

    def w_map(g, j, kk, te, tr):
        on = tr[g] > 0
        return (te[g], jnp.where(on, kk, n_k - 1), jnp.where(on, j, n_j - 1))

    def s_map(g, j, kk, te, tr):
        return (te[g], 0, jnp.where(tr[g] > 0, j, n_j - 1))

    lut, lut_spec = lut_operand(lut, bn, "spx_expert_matmul")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_g, n_j, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), x_map),
            pl.BlockSpec((1, bk, bn // pack_div), w_map),
            pl.BlockSpec((1, 1, bn), s_map),
            lut_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda g, j, kk, te, tr: (g, j)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, group=group, n_k=n_k,
                          out_dtype=out_dtype),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(tile_expert, tile_rows, x, codes, scale, lut)
