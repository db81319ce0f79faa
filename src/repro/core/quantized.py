"""QuantizedTensor: SPx-coded weight container used across the framework.

A QuantizedTensor stores a 2-D (or stacked 3-D+, for scanned layers / experts)
weight as:
  * ``codes``  — uint8 level indices (optionally two int4 codes packed/byte),
  * ``scale``  — per-output-channel alpha (float32, broadcastable),
  * a static codebook identified by ``scheme`` (LUT materialized on demand).

It is registered as a pytree so it flows through jit/pjit/scan like any other
parameter; the static metadata (scheme, packing, logical shape) lives in the
pytree aux data so tracing sees consistent structure.

The matmul entry point here is the *reference* path (pure jnp: LUT gather →
bf16 matmul). The Pallas TPU kernel with in-VMEM dequantization lives in
``repro.kernels`` and is selected by ``repro.kernels.ops.spx_matmul``.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from . import spx

__all__ = ["QuantizedTensor", "quantize_weight", "dequantize", "ref_matmul"]


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    codes: jax.Array            # uint8; last dim possibly packed (int4)
    scale: jax.Array            # f32, broadcastable to logical shape
    scheme: str                 # key into spx.SCHEMES
    packed: bool                # True => two 4-bit codes per byte on last dim

    # -- pytree protocol ----------------------------------------------------
    # NOTE: the logical shape is *derived* from codes (not static aux data) so
    # that lax.scan / vmap can slice stacked QuantizedTensors (leading layer /
    # expert dims) without aux-data mismatches.
    def tree_flatten(self):
        return (self.codes, self.scale), (self.scheme, self.packed)

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, scale = children
        scheme, packed = aux
        return cls(codes, scale, scheme, packed)

    # -- convenience ---------------------------------------------------------
    @property
    def logical_shape(self):
        s = tuple(self.codes.shape)
        return s[:-1] + (s[-1] * 2,) if self.packed else s

    @property
    def shape(self):
        return self.logical_shape

    @property
    def ndim(self):
        return len(self.logical_shape)

    @property
    def lut(self) -> jnp.ndarray:
        return spx.codebook(spx.scheme_levels(self.scheme))

    @property
    def bits(self) -> int:
        return spx.code_width(spx.scheme_levels(self.scheme))

    def nbytes_stored(self) -> int:
        n = int(np.prod(self.logical_shape))
        per = 0.5 if self.packed else 1.0
        return int(n * per) + int(np.prod(self.scale.shape)) * 4

    def dequantize(self, dtype=jnp.bfloat16) -> jax.Array:
        return dequantize(self, dtype)


def quantize_weight(w: jax.Array, scheme: str = "sp2_4", *,
                    contract_axis: int = -2, calibration: str = "mse",
                    pack: bool | None = None) -> QuantizedTensor:
    """Quantize a weight tensor to SPx codes with per-channel calibration.

    Scale (alpha) reduces over ``contract_axis`` only, so it is per output
    channel for 2-D (K, N) weights and per-(expert/layer, channel) for
    stacked (E, K, N) weights. Stacked weights quantize one (K, N) matrix
    at a time: no scale crosses the leading dims, so the result is the
    same, and the calibration's full-size f32 temporaries stay one matrix
    big instead of one stack big (a 28-layer d_ff stack is 1.5 GB each).
    """
    if w.ndim > 2 and contract_axis % w.ndim == w.ndim - 2:
        lead = w.shape[:-2]
        parts = [quantize_weight(w[idx], scheme, calibration=calibration,
                                 pack=pack)
                 for idx in np.ndindex(*lead)]
        codes = jnp.stack([q.codes for q in parts])
        scale = jnp.stack([q.scale for q in parts])
        return QuantizedTensor(codes.reshape(*lead, *codes.shape[1:]),
                               scale.reshape(*lead, *scale.shape[1:]),
                               scheme, parts[0].packed)
    axes = (contract_axis,)
    if calibration == "mse":
        scale = spx.calibrate_mse(w, scheme, axes=axes)
    elif calibration == "minmax":
        scale = spx.calibrate_minmax(w, axes=axes)
    else:
        raise ValueError(f"unknown calibration {calibration!r}")
    levels = spx.scheme_levels(scheme)
    codes = spx.quantize_to_codes(w, levels, scale)
    width = spx.code_width(levels)
    if pack is None:
        pack = width <= 4 and w.shape[-1] % 2 == 0
    if pack and width > 4:
        raise ValueError(f"cannot int4-pack a {width}-bit scheme {scheme!r}")
    if pack:
        codes = spx.pack_int4(codes)
    return QuantizedTensor(codes, scale.astype(jnp.float32), scheme, bool(pack))


def dequantize(qt: QuantizedTensor, dtype=jnp.bfloat16) -> jax.Array:
    codes = spx.unpack_int4(qt.codes) if qt.packed else qt.codes
    return spx.dequantize_codes(codes, qt.lut, qt.scale, dtype=dtype)


def ref_matmul(x: jax.Array, qt: QuantizedTensor, *,
               precision=None, out_dtype=None) -> jax.Array:
    """Reference quantized matmul: x @ dequant(qt). Contracts x's last dim
    with qt's second-to-last logical dim. Works for 2-D and stacked 3-D qt
    (leading dims broadcast/batched by caller)."""
    w = dequantize(qt, dtype=x.dtype)
    out = jax.lax.dot_general(
        x, w,
        dimension_numbers=(((x.ndim - 1,), (w.ndim - 2,)), ((), ())),
        precision=precision)
    return out.astype(out_dtype or x.dtype)
