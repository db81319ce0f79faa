"""Plain SPx quantization for the reference: level sets of sums of powers
of two (sign, then per-term bit widths), nearest-level rounding, and the
per-output-channel scale chosen by squared error over a fixed grid of
fractions of the channel's largest magnitude.

This is the yardstick's own statement of the scheme the served weights
and KV pages use. It shares no code with the program: the reference
quantizes the seeded dense weights itself and compares what the program
serves against a dense forward pass over them.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

#: scheme name -> bit widths of its power-of-two terms
TERMS = {"sp2_4": (2, 1), "spx_8_x3": (3, 2, 2)}

#: weight scales: the squared-error minimum over these fractions of the
#: per-channel largest magnitude
SCALE_FRACTIONS = np.linspace(0.4, 1.05, 24)


@functools.lru_cache(maxsize=None)
def levels(scheme: str) -> np.ndarray:
    """Sorted levels on [-1, 1]: every value of +-(q_1 + ... + q_x) with
    q_i in {0, +-2^-1, ..., +-2^-(2^b_i - 1)}, scaled so the largest is 1."""
    acc = np.array([0.0])
    for b in TERMS[scheme]:
        mags = 0.5 ** np.arange(1, 2 ** b)
        term = np.concatenate([[0.0], mags, -mags])
        acc = (acc[:, None] + term[None, :]).ravel()
    lv = np.unique(np.concatenate([acc, -acc]))
    return lv / np.abs(lv).max()


def _nearest(xn, lv: np.ndarray):
    """Nearest level of each element of ``xn`` (ties to the lower one): a
    select per midpoint, elementwise so that it fuses into one pass."""
    mids = (lv[1:] + lv[:-1]) / 2
    out = jnp.full(xn.shape, np.float32(lv[0]))
    for m, level in zip(mids, lv[1:]):
        out = jnp.where(xn > np.float32(m), np.float32(level), out)
    return out


def qdq_rows(x, scheme: str):
    """Quantize-dequantize each vector along the last axis with its own
    scale, the largest magnitude in it (the KV pages' per token and head
    scale)."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8)
    lv = levels(scheme)
    xn = jnp.clip(x / s, lv[0], lv[-1])
    return _nearest(xn, lv) * s


def qdq_weight(w, scheme: str):
    """Quantize-dequantize a (K, N) weight with one scale per column, the
    fraction of the column's largest magnitude whose rounding error is
    smallest."""
    w = w.astype(jnp.float32)
    lv = levels(scheme)
    base = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12)

    def rounded(scale):
        xn = jnp.clip(w / scale, lv[0], lv[-1])
        return _nearest(xn, lv) * scale

    errs = jnp.stack([jnp.sum((rounded(base * f) - w) ** 2, axis=0)
                      for f in SCALE_FRACTIONS])
    frac = jnp.asarray(SCALE_FRACTIONS, jnp.float32)[jnp.argmin(errs, 0)]
    return rounded(base * frac[None, :])
