"""Mixture-of-Experts: the training layer (expert-parallel, top-k,
capacity-bounded, via shard_map) and the serving layer (dropless, SPx
codes read in place).

Serving (``moe_serve``): every routed (token, expert) pair is computed,
whatever the batch, so a served token's output never depends on its
batch-mates. The pairs are sorted by expert into tiles of rows that each
belong to one expert, and ``kernels.ops.spx_expert_matmul`` multiplies
each tile by its expert's SPx codes, decoded in VMEM: no dequantized
expert stack is ever built. On a mesh the experts shard over the
``model`` axis as in training, and each device computes only the pairs
routed to its own experts.

Training (``moe_apply``):

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
from repro.kernels import ops
from repro.runtime import Runtime

from .layers import dense_apply, dense_init
from .mlp import ACTIVATIONS

__all__ = ["moe_init", "moe_apply", "moe_serve", "expert_capacity",
           "route", "expert_tile_rows", "group_by_expert"]


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


def route(x, router_w, *, top_k: int, renormalize: bool = True):
    """Softmax over every expert's router logit, then the top ``top_k``:
    (weights (T, k) f32, experts (T, k) int32, probs (T, E)).
    ``renormalize`` rescales each token's k weights to sum to 1; without
    it they are the softmax probabilities as they are (Jamba)."""
    # full f32 precision: the choice of experts is discrete, and the
    # (T, D) x (D, E) product is too small to cost anything
    logits = jnp.dot(x, router_w.astype(x.dtype),
                     preferred_element_type=jnp.float32,
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if renormalize:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return top_p, top_e, probs


def _moe_local(x, router_w, gate_w, up_w, down_w, *, top_k: int,
               n_experts_global: int, capacity_factor: float,
               model_axis: str | None, renormalize: bool = True):
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

    top_p, top_e, probs = route(x, router_w, top_k=top_k,
                                renormalize=renormalize)    # (T, k)

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
              capacity_factor: float = 1.25, renormalize: bool = True,
              rt: Runtime):
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
                              model_axis=axis, renormalize=renormalize),
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
                            capacity_factor=capacity_factor, model_axis=None,
                            renormalize=renormalize)
        y = y.reshape(b, s, d)

    if "shared" in p:
        from .mlp import mlp_apply
        y = y + mlp_apply(p["shared"], x, variant="swiglu", rt=rt)
    return y, aux


# ---------------------------------------------------------------------------
# Serving: dropless dispatch over row tiles grouped by expert
# ---------------------------------------------------------------------------

#: rows of one expert tile stay within these bounds (a multiple of the f32
#: sublane count; the top bounds the (tiles x rows, d_ff) intermediates)
TILE_ROWS_MIN, TILE_ROWS_MAX = 8, 512


def expert_tile_rows(n_pairs: int, n_experts: int) -> int:
    """Rows per expert tile for ``n_pairs`` routed pairs: the power of two
    at or above four times an even share, so that an expert's group
    usually fits one tile and its codes are streamed and decoded once."""
    want = 4 * -(-n_pairs // n_experts)
    return int(min(TILE_ROWS_MAX, max(TILE_ROWS_MIN,
                                      1 << (want - 1).bit_length())))


def group_by_expert(experts, n_experts: int, bm: int):
    """Lay routed pairs out in tiles of ``bm`` rows, one expert per tile.

    experts: (n,) int32 expert of each pair. Returns (dest (n,) int32 row
    of each pair in the padded layout, tile_expert (G,), tile_rows (G,))
    with G = ceil(n / bm) + n_experts, the most tiles any routing needs
    (each expert's last tile may be partial). Empty tiles come last and
    repeat the last expert that holds rows."""
    n = experts.shape[0]
    g = -(-n // bm) + n_experts
    counts = jnp.zeros((n_experts,), jnp.int32).at[experts].add(1)
    tiles = (counts + bm - 1) // bm
    tile_end = jnp.cumsum(tiles)                        # (E,)
    tile_start = tile_end - tiles
    pair_start = jnp.cumsum(counts) - counts
    order = jnp.argsort(experts, stable=True)
    sorted_e = experts[order]
    rank = jnp.arange(n, dtype=jnp.int32) - pair_start[sorted_e]
    dest = jnp.zeros((n,), jnp.int32).at[order].set(
        tile_start[sorted_e] * bm + rank)
    gi = jnp.arange(g, dtype=jnp.int32)
    owner = jnp.searchsorted(tile_end, gi, side="right").astype(jnp.int32)
    last = (n_experts - 1 - jnp.argmax((counts > 0)[::-1])).astype(jnp.int32)
    live = gi < tile_end[-1]
    owner = jnp.where(live, jnp.minimum(owner, n_experts - 1), last)
    rows = jnp.clip(counts[owner] - (gi - tile_start[owner]) * bm, 0, bm)
    return dest, owner, jnp.where(live, rows, 0).astype(jnp.int32)


def _expert_matmul(x, w, tile_expert, tile_rows, bm: int, impl: str,
                   mesh=None):
    """Each ``bm``-row tile of x @ its expert's weight of ``w`` (E, K, N):
    SPx codes through ``ops.spx_expert_matmul``, a dense stack one tile's
    expert at a time."""
    if isinstance(w, QuantizedTensor):
        return ops.spx_expert_matmul(x, w, tile_expert, tile_rows, bm=bm,
                                     impl=impl, mesh=mesh)

    def tile(args):
        xt, e, rows = args
        y = jnp.dot(xt, jax.lax.dynamic_index_in_dim(w, e, keepdims=False)
                    .astype(xt.dtype))
        return jnp.where(rows > 0, y, 0)

    g = tile_expert.shape[0]
    out = jax.lax.map(tile, (x.reshape(g, bm, x.shape[-1]), tile_expert,
                             tile_rows))
    return out.reshape(g * bm, -1)


def _moe_serve_local(xf, router_w, gate, up, down, *, top_k: int,
                     n_experts: int, renormalize: bool, impl: str, mesh,
                     model_axis: str | None):
    """Dropless MoE body over the experts held. xf: (T, D), the same on
    every device of ``model_axis``; gate/up/down: this device's slice of
    the experts (E_loc, ...). With ``model_axis`` the pairs routed to
    other devices' experts are parked in a group no tile computes, and
    the partial outputs are summed over the axis."""
    t, d = xf.shape
    e_loc = gate.shape[0]
    with jax.named_scope("moe/router"):
        top_p, top_e, _ = route(xf, router_w, top_k=top_k,
                                renormalize=renormalize)
        experts = top_e.reshape(-1).astype(jnp.int32)
        bm = expert_tile_rows(t * top_k, n_experts)
        if model_axis is None:
            dest, tile_expert, tile_rows = group_by_expert(experts, e_loc,
                                                           bm)
        else:
            local = experts - jax.lax.axis_index(model_axis) * e_loc
            mine = (local >= 0) & (local < e_loc)
            dest, tile_expert, tile_rows = group_by_expert(
                jnp.where(mine, local, e_loc), e_loc + 1, bm)
            held = (tile_expert < e_loc) & (tile_rows > 0)
            tile_rows = jnp.where(held, tile_rows, 0)
            # the parked tiles repeat the last expert computed, so they
            # fetch no codes
            tile_expert = jnp.where(
                tile_expert < e_loc, tile_expert,
                jnp.max(jnp.where(held, tile_expert, 0)))
            top_p = jnp.where(mine.reshape(t, top_k), top_p, 0.0)
        rows = tile_expert.shape[0] * bm
        xs = jnp.zeros((rows, d), xf.dtype).at[dest].set(
            jnp.repeat(xf, top_k, axis=0))
    with jax.named_scope("moe/experts"):
        mm = functools.partial(_expert_matmul, tile_expert=tile_expert,
                               tile_rows=tile_rows, bm=bm, impl=impl,
                               mesh=mesh)
        h = ACTIVATIONS["silu"](mm(xs, gate)) * mm(xs, up)
        ys = mm(h, down)
    with jax.named_scope("moe/combine"):
        y = jnp.sum(ys[dest].reshape(t, top_k, d)
                    * top_p[..., None].astype(ys.dtype), axis=1)
        if model_axis is not None:
            y = jax.lax.psum(y, model_axis)
    return y.astype(xf.dtype)


def moe_serve(p: dict, x: jax.Array, *, top_k: int, n_experts: int,
              renormalize: bool = True, rt: Runtime):
    """Dropless MoE for serving. x: (B, S, D) -> (B, S, D). Every token
    goes through each of its ``top_k`` experts (SwiGLU), weighted by its
    router weight; no capacity, so a token's output is the same whatever
    its batch-mates. On a mesh whose model axis divides the experts each
    device computes the pairs routed to its own slice of them (expert
    parallelism, one psum), as ``moe_apply`` does."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    body = functools.partial(_moe_serve_local, top_k=top_k,
                             n_experts=n_experts, renormalize=renormalize,
                             impl=rt.impl)
    args = (xf, p["router"]["w"], p["gate"], p["up"], p["down"])
    mesh, axis = rt.mesh, rt.model_axis
    if (mesh is not None and axis is not None and mesh.shape[axis] > 1
            and n_experts % mesh.shape[axis] == 0):
        y = jax.shard_map(
            functools.partial(body, mesh=None, model_axis=axis), mesh=mesh,
            in_specs=(P(), P(), P(axis), P(axis), P(axis)), out_specs=P(),
            check_vma=False)(*args)
    else:
        y = body(*args, mesh=mesh, model_axis=None)
    y = y.reshape(b, s, d)
    if "shared" in p:
        from .mlp import mlp_apply
        y = y + mlp_apply(p["shared"], x, variant="swiglu", rt=rt)
    return y
