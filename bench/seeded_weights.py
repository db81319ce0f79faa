"""Dense random weights from ``--seed``, one leaf at a time by name.

Every leaf is a pure function of (seed, name, layer), so the program's
feed and the reference draw the same numbers without either handing the
other anything. Seeds may exceed 32 bits: the key holds both words.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A raw threefry key from a non-negative seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside 0..2**64-1")
    return jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32)


def leaf(key, name: str, layer, shape: tuple, kind: str):
    """The leaf ``name`` of ``layer`` (a traced index is fine; None for
    leaves outside the layer stack).

    kind: ``matrix`` (x @ W layout, std 1/sqrt(fan-in)), ``embed`` (rows
    of std 1/sqrt(width)), ``bias`` (std 0.1) or ``gain`` (1 + std 0.1).
    """
    k = jax.random.fold_in(key, np.uint32(zlib.crc32(name.encode())))
    k = jax.random.fold_in(k, 0 if layer is None else layer)
    z = jax.random.normal(k, shape, jnp.float32)
    if kind == "matrix":
        return z * shape[0] ** -0.5
    if kind == "embed":
        return z * shape[-1] ** -0.5
    if kind == "bias":
        return 0.1 * z
    if kind == "gain":
        return 1.0 + 0.1 * z
    raise ValueError(f"unknown leaf kind {kind!r}")
