"""Expert-parallel Mixture-of-Experts (top-k, capacity-bounded) via shard_map.

Design (DESIGN.md §4): experts shard over the ``model`` axis (expert
parallelism — all assigned expert counts 384/64/16 divide 16), tokens shard
over the data axes. Each (data, model) shard routes its local tokens against
the FULL router (replicated, tiny), processes only its local expert slice,
and a single psum over ``model`` combines expert contributions. No
all-to-all: token activations are replicated across the model axis (they
already are, post-attention), so EP costs one all-reduce of (T_loc, D) —
the same collective class as Megatron TP, and it overlaps with the next
layer's compute under the XLA latency-hiding scheduler.

Capacity-based dispatch keeps shapes static for jit: each expert takes at
most C = ceil(k * T_loc / E * capacity_factor) tokens per shard; overflow
drops (standard in EP training; the router aux loss keeps loads balanced).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantized import QuantizedTensor, dequantize
from repro.runtime import Runtime

from .layers import dense_apply, dense_init
from .mlp import ACTIVATIONS

__all__ = ["moe_init", "moe_apply", "expert_capacity"]


def moe_init(key, d_model: int, d_ff: int, n_experts: int, *,
             n_shared: int = 0, dtype=jnp.float32) -> dict:
    """Experts are stacked swiglu FFNs: gate/up (E, D, F), down (E, F, D)."""
    ks = jax.random.split(key, 5)
    s = d_model ** -0.5
    p = {
        "router": dense_init(ks[0], d_model, n_experts, dtype=jnp.float32),
        "gate": jax.random.normal(ks[1], (n_experts, d_model, d_ff), dtype) * s,
        "up": jax.random.normal(ks[2], (n_experts, d_model, d_ff), dtype) * s,
        "down": jax.random.normal(ks[3], (n_experts, d_ff, d_model), dtype)
                * (d_ff ** -0.5),
    }
    if n_shared:
        from .mlp import mlp_init
        p["shared"] = mlp_init(ks[4], d_model, d_ff * n_shared,
                               variant="swiglu", dtype=dtype)
    return p


def expert_capacity(n_tokens: int, n_experts: int, top_k: int,
                    capacity_factor: float = 1.25) -> int:
    c = int(n_tokens * top_k * capacity_factor / n_experts) + 1
    # round up to a lane-friendly multiple
    return max(8, -(-c // 8) * 8)


def _dense_w(w, dtype):
    return dequantize(w, dtype) if isinstance(w, QuantizedTensor) else w.astype(dtype)


def _moe_local(x, router_w, gate_w, up_w, down_w, *, top_k: int,
               n_experts_global: int, capacity_factor: float,
               model_axis: str | None):
    """Shard-local MoE body.
    x: (T_loc, D) — identical across the model axis.
    gate/up/down_w: this shard's expert slice (E_loc, D, F) / (E_loc, F, D).
    Returns (y (T_loc, D) partial [psum'ed if model_axis], aux losses dict).
    """
    t_loc, d = x.shape
    e_loc = gate_w.shape[0] if not isinstance(gate_w, QuantizedTensor) \
        else gate_w.logical_shape[0]
    shard = jax.lax.axis_index(model_axis) if model_axis else 0
    e0 = shard * e_loc

    logits = jnp.dot(x, router_w.astype(x.dtype),
                     preferred_element_type=jnp.float32)     # (T, E_glob)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)               # (T, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)   # renormalize

    # ---- capacity dispatch for the local expert slice -------------------
    cap = expert_capacity(t_loc, n_experts_global, top_k, capacity_factor)
    flat_e = top_e.reshape(-1)                               # (T*k,)
    flat_p = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(t_loc), top_k)
    local_e = flat_e - e0
    mine = (local_e >= 0) & (local_e < e_loc)
    local_e = jnp.where(mine, local_e, e_loc)                # park foreign in slot E_loc
    # position of each assignment within its expert queue
    onehot = jax.nn.one_hot(local_e, e_loc + 1, dtype=jnp.int32)  # (T*k, E+1)
    pos = jnp.cumsum(onehot, axis=0) * onehot                # running count
    slot = jnp.sum(pos, axis=-1) - 1                         # (T*k,)
    keep = mine & (slot < cap)
    e_idx = jnp.where(keep, local_e, e_loc)                  # drop -> parked row
    s_idx = jnp.where(keep, slot, 0)

    # gather tokens into (E_loc, C, D); parked row is scratch then discarded
    dispatch = jnp.zeros((e_loc + 1, cap), jnp.int32)
    dispatch = dispatch.at[e_idx, s_idx].set(flat_tok, mode="drop")
    valid = jnp.zeros((e_loc + 1, cap), jnp.bool_)
    valid = valid.at[e_idx, s_idx].set(keep, mode="drop")
    xg = jnp.take(x, dispatch[:e_loc].reshape(-1), axis=0)
    xg = xg.reshape(e_loc, cap, d)
    xg = jnp.where(valid[:e_loc][..., None], xg, 0)

    # ---- expert computation (swiglu) -------------------------------------
    gw = _dense_w(gate_w, xg.dtype)
    uw = _dense_w(up_w, xg.dtype)
    dw = _dense_w(down_w, xg.dtype)
    h = ACTIVATIONS["silu"](jnp.einsum("ecd,edf->ecf", xg, gw)) \
        * jnp.einsum("ecd,edf->ecf", xg, uw)
    y_e = jnp.einsum("ecf,efd->ecd", h, dw)                  # (E_loc, C, D)

    # ---- combine back (scatter-add weighted by gates) ---------------------
    w_pair = jnp.zeros((e_loc + 1, cap), jnp.float32)
    w_pair = w_pair.at[e_idx, s_idx].set(jnp.where(keep, flat_p, 0.0),
                                         mode="drop")
    # combine in the activation dtype: <= top_k additions per token, and the
    # (T_loc, D) f32 buffer + f32 psum would dominate the MoE layer's memory
    y = jnp.zeros((t_loc, d), x.dtype)
    y = y.at[dispatch[:e_loc].reshape(-1)].add(
        (y_e * w_pair[:e_loc][..., None].astype(y_e.dtype)).reshape(-1, d)
        .astype(x.dtype), mode="drop")

    if model_axis is not None:
        y = jax.lax.psum(y, model_axis)

    # load-balance aux loss (Switch-style), computed on global stats
    me = jnp.mean(jax.nn.one_hot(top_e[:, 0], n_experts_global), axis=0)
    ce = jnp.mean(probs, axis=0)
    aux = n_experts_global * jnp.sum(me * ce)
    return y.astype(x.dtype), aux


def moe_apply(p: dict, x: jax.Array, *, top_k: int, n_experts: int,
              capacity_factor: float = 1.25, rt: Runtime):
    """x: (B, S, D) -> (B, S, D). Returns (y, aux_loss)."""
    b, s, d = x.shape
    router_w = p["router"]["w"]

    n_tok = b * s
    n_data = 1
    for a in (rt.data_axes or ()):
        n_data *= dict(rt.mesh.shape)[a] if rt.mesh is not None else 1
    if (rt.mesh is not None and rt.model_axis is not None
            and n_experts % rt.mesh.shape[rt.model_axis] == 0
            and n_tok % max(n_data, 1) == 0):
        axis = rt.model_axis
        dp = rt.data_axes if rt.data_axes else None
        fn = jax.shard_map(
            functools.partial(_moe_local, top_k=top_k,
                              n_experts_global=n_experts,
                              capacity_factor=capacity_factor,
                              model_axis=axis),
            mesh=rt.mesh,
            in_specs=(P(dp, None), P(), P(axis, None, None),
                      P(axis, None, None), P(axis, None, None)),
            out_specs=(P(dp, None), P()),
            check_vma=False,
        )
        xf = x.reshape(b * s, d)
        y, aux = fn(xf, router_w, p["gate"], p["up"], p["down"])
        y = y.reshape(b, s, d)
    else:
        y, aux = _moe_local(x.reshape(b * s, d), router_w, p["gate"], p["up"],
                            p["down"], top_k=top_k,
                            n_experts_global=n_experts,
                            capacity_factor=capacity_factor, model_axis=None)
        y = y.reshape(b, s, d)

    if "shared" in p:
        from .mlp import mlp_apply
        y = y + mlp_apply(p["shared"], x, variant="swiglu", rt=rt)
    return y, aux
