"""SPx non-uniform quantization (the paper's §3.2, Eq. 3.1 / 3.3 / 3.4).

The paper generalizes Power-of-Two (PoT) quantization to *sums of x
power-of-two terms*:

    Q(b, alpha) = ±alpha * sum_i q_i,
    q_i in {0, ±1/2^(2^{b_i}-1), ±1/2^(2^{b_i}-2), ..., ±1/2},
    b = 1 (sign) + sum_i b_i.

x = 1 recovers PoT (Eq. 3.1); x = 2 recovers SP2 of Chang et al. (HPCA'21,
Eq. 3.3). Larger x buys resolution near the tail ends ±alpha where PoT's
levels collapse, at the cost of more shift-add terms on the FPGA — on TPU the
cost is a (slightly) larger codebook LUT, which is free in VMEM.

Everything in this module is pure level-set / codebook math, independent of
where the codes are used (weights, optimizer moments, gradient compression).
All quantize/dequantize functions are jit-traceable.
"""
from __future__ import annotations

import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "pot_levels",
    "sp2_levels",
    "spx_levels",
    "uniform_levels",
    "codebook",
    "quantize_to_codes",
    "dequantize_codes",
    "quantize",
    "fake_quantize",
    "calibrate_minmax",
    "calibrate_mse",
    "int4_group",
    "pack_int4",
    "unpack_int4",
    "SCHEMES",
    "scheme_levels",
    "KV_CODE_BYTES",
    "KV_SCALE_BYTES",
    "kv_token_side_bytes",
    "kv_scale_lanes",
    "kv_page_side_bytes",
]

#: Quantized KV-cache storage layout (scheme-independent; the single owner
#: of these constants — serving/kv_cache.py, runtime/planner.py and
#: benchmarks/roofline.py all derive their byte math from here): one uint8
#: code per element plus one f32 scale per (token, head) side.
KV_CODE_BYTES = 1
KV_SCALE_BYTES = 4


def kv_token_side_bytes(dh: int) -> int:
    """Bytes one token's K *or* V occupies for one KV head in the
    quantized codes+scale cache layout."""
    return dh * KV_CODE_BYTES + KV_SCALE_BYTES


def kv_scale_lanes(page_size: int) -> int:
    """Lanes of one page's scale row in the paged quantized pool: the
    scales of a page's tokens lie along the lanes of one (Hkv, lanes)
    block, padded to whole 128-lane vregs — the layout XLA and the Pallas
    kernels both keep row-major, so neither relayouts the pool."""
    return -(-page_size // 128) * 128


def kv_page_side_bytes(dh: int, page_size: int) -> int:
    """Device bytes of one page of one KV head's K *or* V in the paged
    quantized pool: the codes, plus the page's scale row, padded to
    ``kv_scale_lanes`` — a little more than ``page_size`` x
    ``kv_token_side_bytes``."""
    return (page_size * dh * KV_CODE_BYTES
            + kv_scale_lanes(page_size) * KV_SCALE_BYTES)


# ---------------------------------------------------------------------------
# Level-set construction (numpy; done once per scheme, cached)
# ---------------------------------------------------------------------------

def _single_term_set(b_i: int) -> np.ndarray:
    """q_i in {0, ±1/2^(2^{b_i}-1), ..., ±1/2}  (paper Eq. 3.4, inner set)."""
    if b_i <= 0:
        return np.array([0.0])
    exps = np.arange(1, 2 ** b_i)          # 1 .. 2^{b_i}-1
    mags = 0.5 ** exps                     # 1/2 .. 1/2^(2^{b_i}-1)
    return np.concatenate([[0.0], mags, -mags])


@functools.lru_cache(maxsize=None)
def spx_levels(term_bits: tuple[int, ...]) -> np.ndarray:
    """Canonical SPx level set on [-1, 1] for the given per-term bit widths.

    Implements Eq. 3.4: levels are all distinct values of ±sum_i q_i. The
    overall sign bit is implied by the ± closure of the inner sets, and the
    result always contains ±max and 0. Returned sorted ascending.
    """
    acc = np.array([0.0])
    for b_i in term_bits:
        term = _single_term_set(int(b_i))
        acc = (acc[:, None] + term[None, :]).ravel()
    # ± closure (paper writes ±alpha * {sum}), dedupe on a fixed grid to kill
    # float fuzz (levels are dyadic rationals, exactly representable).
    acc = np.concatenate([acc, -acc])
    levels = np.unique(acc)
    # Normalize so the largest magnitude is exactly 1 (alpha carries scale).
    m = np.abs(levels).max()
    if m > 0:
        levels = levels / m
    return levels.astype(np.float64)


@functools.lru_cache(maxsize=None)
def pot_levels(b: int) -> np.ndarray:
    """Eq. 3.1: alpha * {0, ±1/2^(2^{b-1}-1), ..., ±1/2, ±1}."""
    exps = np.arange(0, 2 ** (b - 1))      # 0 .. 2^{b-1}-1
    mags = 0.5 ** exps                     # 1, 1/2, ..., 1/2^(2^{b-1}-1)
    levels = np.unique(np.concatenate([[0.0], mags, -mags]))
    return levels.astype(np.float64)


def sp2_levels(b: int) -> np.ndarray:
    """Eq. 3.3 with the balanced split b1 + b2 = b - 1 (Chang et al.)."""
    b1 = (b - 1 + 1) // 2
    b2 = (b - 1) - b1
    return spx_levels((b1, b2))


@functools.lru_cache(maxsize=None)
def uniform_levels(b: int) -> np.ndarray:
    """Symmetric uniform b-bit levels (the §3.2.A baseline)."""
    n = 2 ** (b - 1) - 1
    return (np.arange(-n, n + 1) / n).astype(np.float64)


#: Named schemes used across the framework. Values are (family, arg). Scheme
#: names carry the *code width* (bits to index the level set) — note Eq. 3.4's
#: b = sum(b_i) does not in general equal the code width because sums of PoT
#: terms collide; we name by what HBM actually stores.
SCHEMES = {
    "uniform8": ("uniform", 8),
    "uniform4": ("uniform", 4),
    "pot4": ("pot", 4),
    "pot3": ("pot", 3),
    "sp2_4": ("spx", (2, 1)),        # 4-bit SP2 (15 levels)
    "sp2_8": ("spx", (4, 2)),        # 8-bit SP2 (179 levels)
    "spx_5_x3": ("spx", (2, 2, 1)),  # 5-bit, x=3 terms — the paper's extension
    "spx_8_x3": ("spx", (3, 2, 2)),  # 8-bit, x=3 terms (131 levels)
}


def scheme_levels(scheme: str) -> np.ndarray:
    family, arg = SCHEMES[scheme]
    if family == "uniform":
        return uniform_levels(arg)
    if family == "pot":
        return pot_levels(arg)
    if family == "spx":
        return spx_levels(tuple(arg))
    raise ValueError(f"unknown scheme family {family!r}")


def code_width(levels: np.ndarray | Sequence[float]) -> int:
    """Bits needed to index the level set."""
    n = len(levels)
    return max(1, int(np.ceil(np.log2(n))))


# ---------------------------------------------------------------------------
# Codebook quantize / dequantize (jit-traceable)
# ---------------------------------------------------------------------------

def codebook(levels: np.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """Levels as a device LUT, padded to the next power of two so that codes
    fill the integer range (padding repeats the last level — harmless, those
    codes are never produced by quantize)."""
    n = len(levels)
    size = 2 ** code_width(levels)
    padded = np.concatenate([levels, np.full(size - n, levels[-1])])
    return jnp.asarray(padded, dtype=dtype)


def _midpoints(levels: np.ndarray) -> np.ndarray:
    return (levels[1:] + levels[:-1]) / 2.0


def quantize_to_codes(x: jax.Array, levels: np.ndarray, scale: jax.Array) -> jax.Array:
    """Nearest-level codes for x given per-channel `scale` (broadcastable).

    Nearest-neighbour on a sorted level set == searchsorted over midpoints.
    Returns uint8 codes (all schemes here are <= 8 bit).
    """
    mids = jnp.asarray(_midpoints(levels), dtype=jnp.float32)
    xn = (x / scale).astype(jnp.float32)
    xn = jnp.clip(xn, float(levels[0]), float(levels[-1]))
    codes = jnp.searchsorted(mids, xn, side="left")
    return codes.astype(jnp.uint8)


def dequantize_codes(codes: jax.Array, lut: jax.Array, scale: jax.Array,
                     dtype=jnp.bfloat16) -> jax.Array:
    """codes -> lut[codes] * scale. `lut` from `codebook()`."""
    vals = jnp.take(lut, codes.astype(jnp.int32), axis=0)
    return (vals * scale).astype(dtype)


def quantize(x: jax.Array, scheme: str, scale: jax.Array) -> jax.Array:
    return quantize_to_codes(x, scheme_levels(scheme), scale)


def fake_quantize(x: jax.Array, scheme: str, scale: jax.Array,
                  dtype=None) -> jax.Array:
    """Quantize-dequantize round trip (QAT / error-feedback building block)."""
    levels = scheme_levels(scheme)
    codes = quantize_to_codes(x, levels, scale)
    out = dequantize_codes(codes, codebook(levels), scale, dtype=jnp.float32)
    return out.astype(dtype or x.dtype)


# ---------------------------------------------------------------------------
# Calibration of alpha (per-channel scale)
# ---------------------------------------------------------------------------

def _reduce_axes(x: jax.Array, channel_axis: int | None,
                 axes: tuple | None = None):
    if axes is not None:
        return tuple(a % x.ndim for a in axes)
    if channel_axis is None:
        return tuple(range(x.ndim))
    channel_axis = channel_axis % x.ndim
    return tuple(a for a in range(x.ndim) if a != channel_axis)


def calibrate_minmax(x: jax.Array, channel_axis: int | None = -1,
                     axes: tuple | None = None) -> jax.Array:
    """alpha = max|x| per channel (keepdims, broadcastable against x).

    ``axes`` overrides ``channel_axis``: reduce exactly those axes (used for
    stacked expert/layer weights where only the contracting dim reduces)."""
    axes = _reduce_axes(x, channel_axis, axes)
    a = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    return jnp.maximum(a, 1e-12)


def calibrate_mse(x: jax.Array, scheme: str, channel_axis: int | None = -1,
                  num_grid: int = 24, lo: float = 0.4, hi: float = 1.05,
                  axes: tuple | None = None) -> jax.Array:
    """MSE-optimal alpha: sweep a grid of fractions of max|x| per channel and
    pick the scale minimizing quantization MSE. Cheap (done offline, once per
    weight), and markedly better than minmax for heavy-tailed weights — this
    is where SPx's tail resolution (the paper's selling point) actually shows.
    """
    levels = scheme_levels(scheme)
    lut = codebook(levels)
    base = calibrate_minmax(x, channel_axis, axes)
    fracs = np.linspace(lo, hi, num_grid)
    axes = _reduce_axes(x, channel_axis, axes)

    def err_for(frac):
        scale = base * frac
        codes = quantize_to_codes(x, levels, scale)
        xh = dequantize_codes(codes, lut, scale, dtype=jnp.float32)
        return jnp.sum((xh - x.astype(jnp.float32)) ** 2, axis=axes, keepdims=True)

    def keep_better(best, frac):
        # one fraction at a time: the codes and the dequantized copy of
        # ``x`` are live for one fraction, not for all of the grid at once
        # (24 of them are 8 GB for one 4096 x 16384 weight); the strict <
        # keeps the first of equal errors, as an argmin over the grid would
        err, fr = best
        e = err_for(frac)
        return (jnp.where(e < err, e, err), jnp.where(e < err, frac, fr)), None

    init = (jnp.full(base.shape, jnp.inf, jnp.float32),
            jnp.zeros(base.shape, jnp.float32))
    (_, fr), _ = jax.lax.scan(keep_better, init,
                              jnp.asarray(fracs, jnp.float32))
    return base * fr


# ---------------------------------------------------------------------------
# int4 packing (two codes per byte) — halves HBM traffic again for b<=4
# ---------------------------------------------------------------------------

def int4_group(n: int) -> int:
    """Lane-group width of the int4 packing along a last dim of ``n``
    codes: column c of each ``2 * G``-column group shares a byte with
    column c + G. ``G = 128`` (one TPU lane tile) whenever ``n`` allows
    it, so a kernel unpacks a tile by concatenating two lane-aligned
    halves — no interleave; otherwise one group spans the whole dim."""
    return 128 if n % 256 == 0 else n // 2


def pack_int4(codes: jax.Array) -> jax.Array:
    """Pack uint8 codes (<16) two per byte along the LAST axis: within
    each group of ``2 * int4_group(n)`` columns, the first half goes to
    the low nibble and the second half to the high nibble. Last dim must
    be even."""
    n = codes.shape[-1]
    if n % 2:
        raise ValueError(
            f"pack_int4 needs an even last dim (two 4-bit codes per byte); "
            f"got shape {tuple(codes.shape)} with last dim "
            f"{n}. Pad the weight or pass pack=False.")
    g = int4_group(n)
    grouped = codes.reshape(*codes.shape[:-1], n // (2 * g), 2, g)
    lo, hi = grouped[..., 0, :], grouped[..., 1, :]
    packed = (lo | (hi << 4)).astype(jnp.uint8)
    return packed.reshape(*codes.shape[:-1], n // 2)


def unpack_int4(packed: jax.Array) -> jax.Array:
    """Inverse of pack_int4; doubles the last axis."""
    n = packed.shape[-1] * 2
    g = int4_group(n)
    grouped = packed.reshape(*packed.shape[:-1], n // (2 * g), g)
    out = jnp.stack([grouped & 0x0F, (grouped >> 4) & 0x0F], axis=-2)
    return out.reshape(*packed.shape[:-1], n).astype(jnp.uint8)
