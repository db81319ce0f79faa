"""In-kernel decode of SPx codes, shared by the Pallas kernels.

Mosaic lowers neither a gather from a 1-D table nor 8-bit shifts and lane
interleaves, so both steps here use only what it accepts: codes widen to
int32 first, and packed int4 tiles unpack as two lane-aligned halves
(``core/spx.pack_int4`` groups 128 columns to the low nibble and the next
128 to the high nibble).

The codebook lookup takes one of two forms, fixed at trace time by what
the kernel sees (``lut_operand``):

- a lane gather (``tpu.dynamic_gather``) where the table has more than 16
  entries and the code tile's last dim is a multiple of 128: the table
  sits in VMEM as rows of 128 levels, each 128-lane slice of the tile
  gathers ``codes & 127`` from every row, and the code's high bits pick
  the row — a handful of vector ops per element for any table size;
- elsewhere a select chain over the table, one scalar read from SMEM per
  entry, which Mosaic unrolls for the 16-entry int4 tables.

Both move the table's own f32 values, so the result is exactly
``lut[codes]`` and interpret-mode kernels stay bit-identical to the jnp
oracles in ``kernels/ref.py``.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["unpack_int4_tile", "lut_decode", "lut_operand", "LOOKUPS"]

_LANES = 128

#: trace-time count of the lookup each kernel was built with, keyed by
#: (kernel, "gather" | "select"); read through ``ops.lut_lookups()``
LOOKUPS: collections.Counter = collections.Counter()


def unpack_int4_tile(packed, group: int):
    """(rows, cols/2) uint8 -> (rows, cols) int32 under the lane-grouped
    int4 layout with group width ``group`` (``spx.int4_group``)."""
    c = packed.astype(jnp.int32)
    lo, hi = c & 0x0F, (c >> 4) & 0x0F
    parts = []
    for g in range(c.shape[1] // group):
        sl = slice(g * group, (g + 1) * group)
        parts += [lo[:, sl], hi[:, sl]]
    return jnp.concatenate(parts, axis=1)


def lut_operand(lut, width: int, kernel: str):
    """The codebook ``lut`` ((n,) f32) as a ``pallas_call`` operand and
    its BlockSpec, for code tiles ``width`` lanes wide: (rows, 128) VMEM
    rows for the lane gather, else the 1-D table in SMEM for the select
    chain. ``kernel`` names the caller in ``LOOKUPS``."""
    n = lut.shape[0]
    if n > 16 and width % _LANES == 0:
        LOOKUPS[kernel, "gather"] += 1
        rows = -(-n // _LANES)
        # codes stay below n, so the padding is never read
        table = jnp.pad(lut, (0, rows * _LANES - n)).reshape(rows, _LANES)
        return table, pl.BlockSpec((rows, _LANES), lambda *_: (0, 0))
    LOOKUPS[kernel, "select"] += 1
    return lut, pl.BlockSpec(memory_space=pltpu.SMEM)


def lut_decode(codes, lut_ref):
    """``lut[codes]`` as f32 for a 2-D int32 code tile, against the table
    ``lut_operand`` placed: gathered from its VMEM rows, or selected from
    SMEM one entry at a time."""
    if len(lut_ref.shape) == 2:
        return _gather_decode(codes, lut_ref[...])

    def body(i, acc):
        return jnp.where(codes == i, lut_ref[i], acc)
    n = lut_ref.shape[0]
    # Mosaic unrolls a loop fully or not at all: the 16-entry int4 table
    # unrolls, the longer ones stay a loop
    return jax.lax.fori_loop(0, n, body, jnp.zeros(codes.shape, jnp.float32),
                             unroll=n <= 16)


def _gather_decode(codes, table):
    rows = [jnp.broadcast_to(table[r:r + 1], (codes.shape[0], _LANES))
            for r in range(table.shape[0])]
    low, high = codes & (_LANES - 1), codes >> 7     # 128 = 2**7
    parts = []
    for s in range(0, codes.shape[1], _LANES):
        lane = low[:, s:s + _LANES]
        out = jnp.take_along_axis(rows[0], lane, axis=1,
                                  mode="promise_in_bounds")
        for r in range(1, len(rows)):
            out = jnp.where(high[:, s:s + _LANES] == r,
                            jnp.take_along_axis(rows[r], lane, axis=1,
                                                mode="promise_in_bounds"),
                            out)
        parts.append(out)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
