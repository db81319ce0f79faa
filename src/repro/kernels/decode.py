"""In-kernel decode of SPx codes, shared by the Pallas kernels.

Mosaic lowers neither a gather from a 1-D table nor 8-bit shifts and lane
interleaves, so both steps here use only what it accepts: codes widen to
int32 first, packed int4 tiles unpack as two lane-aligned halves
(``core/spx.pack_int4`` groups 128 columns to the low nibble and the next
128 to the high nibble), and the codebook lookup is a select chain over
the table, one scalar read from SMEM per entry. The result is exactly
``lut[codes]``, so interpret-mode kernels stay bit-identical to the jnp
oracles in ``kernels/ref.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["unpack_int4_tile", "lut_decode", "lut_spec"]


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


def lut_decode(codes, lut_ref):
    """``lut[codes]`` as f32 without a gather: codes (int32, any 2-D
    shape) select their level from the SMEM table ``lut_ref`` one entry
    at a time."""
    def body(i, acc):
        return jnp.where(codes == i, lut_ref[i], acc)
    n = lut_ref.shape[0]
    # Mosaic unrolls a loop fully or not at all: the 16-entry int4 table
    # unrolls, the 256-entry 8-bit one stays a loop
    return jax.lax.fori_loop(0, n, body, jnp.zeros(codes.shape, jnp.float32),
                             unroll=n <= 16)


def lut_spec():
    """BlockSpec placing the whole codebook in SMEM for the launch."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)
