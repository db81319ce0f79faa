"""Minimal functional module substrate: params are plain pytrees (nested
dicts of arrays and/or QuantizedTensors); every module is an init fn plus an
apply fn. No framework dependency — jit/pjit/scan compose directly.

Any 2-D+ weight may be a ``QuantizedTensor`` (the paper's SPx codes) instead
of a dense array; ``dense_apply`` transparently routes through the pipelined
quantized matmul (`repro.kernels.ops.spx_matmul`).
"""
from __future__ import annotations

from typing import Any, TYPE_CHECKING

import jax
import jax.numpy as jnp

from repro.core.quantized import QuantizedTensor
from repro.kernels import ops

if TYPE_CHECKING:                 # annotations only — import Runtime from
    from repro.runtime import Runtime   # repro.runtime, not this module

__all__ = [
    "dense_init", "dense_apply", "embedding_init",
    "embedding_apply", "rmsnorm_init", "rmsnorm_apply", "layernorm_init",
    "layernorm_apply", "norm_init", "norm_apply", "quantize_params",
    "param_count", "opt_barrier",
]


@jax.custom_vjp
def opt_barrier(x):
    """optimization_barrier with an identity gradient. This jax version has
    no differentiation rule for the barrier primitive; its job here (block
    f32-convert fusion into residual-stack / checkpoint saves) is a
    forward-pass layout concern, so the backward passes cotangents through
    untouched. Accepts pytrees."""
    return jax.lax.optimization_barrier(x)


opt_barrier.defvjp(lambda x: (jax.lax.optimization_barrier(x), None),
                   lambda _, g: (g,))


# ---------------------------------------------------------------------------
# Dense / Embedding / Norms
# ---------------------------------------------------------------------------

def constrain_feature_sharded(x: jax.Array, rt: "Runtime | None"):
    """Constrain a (B, S, F) activation to shard F over the model axis
    (batch over data). Used inside SSM mixers where every op is pointwise
    over F — keeps GSPMD from propagating sequence sharding into the causal
    conv (whose halo forces a full-sequence all-gather)."""
    if rt is None or rt.mesh is None or x.ndim != 3:
        return x
    n_model = dict(rt.mesh.shape).get(rt.model_axis, 1)
    if x.shape[-1] % n_model:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp = rt.data_axes if rt.data_axes else None
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(rt.mesh, P(dp, None, rt.model_axis)))


def dense_init(key, d_in: int, d_out: int, *, bias: bool = False,
               dtype=jnp.float32, scale: float | None = None) -> dict:
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": jax.random.normal(key, (d_in, d_out), dtype) * scale}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p: dict, x: jax.Array, rt: Runtime | None = None) -> jax.Array:
    w = p["w"]
    if isinstance(w, QuantizedTensor):
        y = ops.spx_matmul(x, w, impl=(rt.impl if rt else "auto"),
                           mesh=(rt.mesh if rt else None))
    else:
        y = jnp.dot(x, w.astype(x.dtype))
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def embedding_init(key, vocab: int, d_model: int, *, dtype=jnp.float32) -> dict:
    return {"table": jax.random.normal(key, (vocab, d_model), dtype)
            * (d_model ** -0.5)}


def embedding_apply(p: dict, ids: jax.Array) -> jax.Array:
    return jnp.take(p["table"], ids, axis=0)


def embedding_attend(p: dict, x: jax.Array) -> jax.Array:
    """Tied readout: x @ table^T."""
    t = p["table"]
    return jnp.dot(x, t.astype(x.dtype).T)


def rmsnorm_init(d: int, dtype=jnp.float32) -> dict:
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm_apply(p: dict, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    # statistics accumulate in f32 (einsum with preferred f32) but x itself
    # is never materialized in f32: an upcast here gets fused by XLA into
    # the *collectives* feeding the norm, doubling SP all-gather bytes
    # (§Perf iteration 5 in EXPERIMENTS.md)
    d = x.shape[-1]
    ss = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32)
    scale = jax.lax.rsqrt(ss / d + eps)[..., None].astype(x.dtype)
    return x * scale * p["g"].astype(x.dtype)


def layernorm_init(d: int, dtype=jnp.float32) -> dict:
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm_apply(p: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    d = x.shape[-1]
    one = jnp.ones((d,), x.dtype)
    s1 = jnp.einsum("...d,d->...", x, one,
                    preferred_element_type=jnp.float32)
    s2 = jnp.einsum("...d,...d->...", x, x,
                    preferred_element_type=jnp.float32)
    mu = s1 / d
    var = jnp.maximum(s2 / d - mu * mu, 0.0)
    scale = jax.lax.rsqrt(var + eps)
    out = (x - mu[..., None].astype(x.dtype)) \
        * scale[..., None].astype(x.dtype)
    return out * p["g"].astype(x.dtype) + p["b"].astype(x.dtype)


def norm_init(kind: str, d: int, dtype=jnp.float32) -> dict:
    return rmsnorm_init(d, dtype) if kind == "rmsnorm" else layernorm_init(d, dtype)


def norm_apply(kind: str, p: dict, x: jax.Array) -> jax.Array:
    return rmsnorm_apply(p, x) if kind == "rmsnorm" else layernorm_apply(p, x)


# ---------------------------------------------------------------------------
# Whole-tree helpers
# ---------------------------------------------------------------------------

#: sensitive / non-matmul params kept dense: embeddings, biases, norm gains,
#: router, SSM dynamics (A_log, D, dt, convs), sLSTM recurrence (r), head
_NO_QUANT_KEYS = ("table", "b", "g", "router", "A_log", "dt", "D",
                  "conv_b", "conv_w", "head", "out_norm_g", "r")


def quantize_params(params: Any, scheme: str = "sp2_4", *,
                    min_size: int = 4096, calibration: str = "mse") -> Any:
    """Replace every >=2-D weight leaf with >= ``min_size`` elements by its
    SPx QuantizedTensor (per-output-channel alpha). Norm gains, biases,
    embedding tables, routers, SSM dynamics params and small tensors stay
    dense. This is the paper's deployment step."""
    from repro.core.quantized import quantize_weight

    def maybe_q(path, leaf):
        if isinstance(leaf, QuantizedTensor):
            return leaf
        keys = {getattr(k, "key", getattr(k, "name", None)) for k in path}
        if keys & set(_NO_QUANT_KEYS):
            return leaf
        if (hasattr(leaf, "ndim") and leaf.ndim >= 2
                and leaf.size >= min_size
                and jnp.issubdtype(leaf.dtype, jnp.floating)
                and leaf.shape[-1] % 2 == 0):
            return quantize_weight(leaf, scheme, calibration=calibration)
        return leaf

    # a QuantizedTensor is itself a pytree: without is_leaf the walk would
    # reach its scale array and quantize that a second time
    return jax.tree_util.tree_map_with_path(
        maybe_q, params, is_leaf=lambda l: isinstance(l, QuantizedTensor))


def param_count(params: Any) -> int:
    leaves = jax.tree_util.tree_leaves(
        params, is_leaf=lambda l: isinstance(l, QuantizedTensor))
    total = 0
    for l in leaves:
        if isinstance(l, QuantizedTensor):
            total += int(jnp.prod(jnp.array(l.logical_shape)))
        else:
            total += l.size
    return total
