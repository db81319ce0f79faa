"""State-space / recurrent blocks: Mamba (Jamba's mixer) and xLSTM's
mLSTM + sLSTM. All are attention-free (O(1) state per token -> they carry
the ``long_500k`` shape), and their projection matmuls route through the
quantized dense path like every other linear.

Training uses lax.scan over the sequence (a While loop in HLO — its
elementwise body is <1% of layer FLOPs; see DESIGN.md §6 on cost
accounting). Decode is a single-step state update.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.runtime import Runtime

from .layers import (constrain_feature_sharded, dense_apply, dense_init,
                     opt_barrier, rmsnorm_apply, rmsnorm_init)

__all__ = [
    "mamba_init", "mamba_apply", "mamba_decode_step", "mamba_init_state",
    "mamba_paged_step",
    "mlstm_init", "mlstm_apply", "mlstm_decode_step", "mlstm_init_state",
    "mlstm_paged_step",
    "slstm_init", "slstm_apply", "slstm_decode_step", "slstm_init_state",
    "slstm_paged_step",
]


# ===========================================================================
# Mamba (selective SSM, mamba-1 form used by Jamba)
# ===========================================================================

def mamba_init(key, d_model: int, *, d_state: int = 16, d_conv: int = 4,
               expand: int = 2, dt_rank: int | None = None,
               bcdt_norm: bool = False, dtype=jnp.float32) -> dict:
    """``bcdt_norm``: RMSNorm gains for the x_proj outputs (dt, B, C),
    applied before dt_proj and the scan wherever they are present."""
    di = expand * d_model
    dt_rank = dt_rank or max(16, d_model // 16)
    ks = jax.random.split(key, 6)
    p = {
        "in_proj": dense_init(ks[0], d_model, 2 * di, dtype=dtype),
        "conv_w": jax.random.normal(ks[1], (d_conv, di), dtype) * 0.2,
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": dense_init(ks[2], di, dt_rank + 2 * d_state, dtype=dtype),
        "dt_proj": {"w": jax.random.normal(ks[3], (dt_rank, di), dtype)
                    * dt_rank ** -0.5,
                    "b": jnp.log(jnp.exp(jnp.full((di,), 0.01)) - 1.0)
                    .astype(dtype)},
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, d_state + 1, dtype=jnp.float32), (di, d_state))
        ).astype(dtype),
        "D": jnp.ones((di,), dtype),
        "out_proj": dense_init(ks[4], di, d_model, dtype=dtype),
    }
    if bcdt_norm:
        p.update(dt_norm=rmsnorm_init(dt_rank, dtype),
                 b_norm=rmsnorm_init(d_state, dtype),
                 c_norm=rmsnorm_init(d_state, dtype))
    return p


def _mamba_dims(p):
    d_conv, di = p["conv_w"].shape
    d_state = p["A_log"].shape[1]
    dt_rank = p["dt_proj"]["w"].shape[0]
    return di, d_state, d_conv, dt_rank


def mamba_init_state(p, batch: int, dtype=jnp.float32):
    di, d_state, d_conv, _ = _mamba_dims(p)
    return {"h": jnp.zeros((batch, di, d_state), jnp.float32),
            "conv": jnp.zeros((batch, d_conv - 1, di), dtype)}


def _causal_depthwise_conv(x, w, b):
    """x: (B, S, di); w: (dc, di). Causal, per-channel."""
    dc = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (dc - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for j in range(dc):  # dc is 4: unrolled taps, no While loop
        out = out + pad[:, j:j + x.shape[1], :] * w[j]
    return out + b


def _ssm_inputs(p, proj, dt_rank: int, d_state: int):
    """Split the x_proj output into (dt low-rank, B, C), each through its
    RMSNorm where the block has them (Jamba)."""
    dt, Bm, Cm = jnp.split(proj, [dt_rank, dt_rank + d_state], axis=-1)
    if "dt_norm" in p:
        dt = rmsnorm_apply(p["dt_norm"], dt)
        Bm = rmsnorm_apply(p["b_norm"], Bm)
        Cm = rmsnorm_apply(p["c_norm"], Cm)
    return dt, Bm, Cm


SSM_CHUNK = 128


def _selective_scan(u, dt, A, Bm, Cm, D, h0, *, chunk: int = SSM_CHUNK,
                    unroll: bool = False):
    """Chunked selective scan. u, dt: (B,S,di); A: (di,ds); Bm/Cm: (B,S,ds);
    h0: (B,di,ds). Returns y (B,S,di), hT.

    The (B,S,di,ds) state tensor is never materialized for the full
    sequence: an outer lax.scan carries h across chunks of ``chunk`` steps;
    inside a chunk, an associative scan over (decay, input) pairs computes
    all within-chunk states in parallel form. Each chunk body is remat'd so
    the backward recomputes it — saved residuals are one (B,di,ds) carry
    per chunk instead of per step (the difference between 1GB and 68GB per
    device for Jamba's train_4k)."""
    b, s, di = u.shape
    ds = A.shape[1]
    c = min(chunk, s)
    if s % c:
        c = s
    nc = s // c

    def chunk_xs(x):
        return x.reshape(b, nc, c, *x.shape[2:]).swapaxes(0, 1)

    xs = (chunk_xs(u), chunk_xs(dt), chunk_xs(Bm), chunk_xs(Cm))

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def chunk_body(h, inp):
        inp = opt_barrier(inp)
        u_c, dt_c, B_c, C_c = inp                          # (B,c,di), (B,c,ds)
        # f32 only per chunk-slice — full-sequence (B,S,di) tensors stay in
        # the model's compute dtype (bf16 at production scale)
        u32 = u_c.astype(jnp.float32)
        dt32 = dt_c.astype(jnp.float32)
        dA = jnp.exp(dt32[..., None] * A)                  # (B,c,di,ds)
        dBu = dt32[..., None] * B_c.astype(jnp.float32)[:, :, None, :] \
            * u32[..., None]
        # h_t = dA_t h_{t-1} + dBu_t  via associative composition
        # (A2, b2) o (A1, b1) = (A2*A1, A2*b1 + b2), scanned along c
        def compose(lhs, rhs):
            a1, b1 = lhs
            a2, b2 = rhs
            return a2 * a1, a2 * b1 + b2
        acc_a, acc_b = jax.lax.associative_scan(compose, (dA, dBu), axis=1)
        hs = acc_a * h[:, None] + acc_b                    # (B,c,di,ds)
        y_c = jnp.einsum("bcds,bcs->bcd", hs,
                         C_c.astype(jnp.float32))
        return hs[:, -1], y_c.astype(u_c.dtype)

    hT, ys = jax.lax.scan(chunk_body, h0, xs,
                          unroll=True if unroll else 1)
    y = ys.swapaxes(0, 1).reshape(b, s, di) \
        + u * D.astype(u.dtype)
    return y, hT


def _sequential_scan(u, dt, A, Bm, Cm, D, h0):
    """The selective scan one position at a time, for the few positions
    of a serving chunk. u, dt: (B,C,di); A: (di,ds); Bm/Cm: (B,C,ds); h0:
    (B,di,ds). Returns y (B,C,di), hT. Only one (B,di,ds) state is live
    at a time: the parallel form's (B,C,di,ds) tensors would take
    gigabytes at a full batch of published-width slots."""
    def step(h, inp):
        u_t, dt_t, b_t, c_t = (a.astype(jnp.float32) for a in inp)
        h = jnp.exp(dt_t[..., None] * A) * h \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return h, jnp.einsum("bds,bs->bd", h, c_t)

    hT, ys = jax.lax.scan(step, h0, tuple(a.swapaxes(0, 1)
                                          for a in (u, dt, Bm, Cm)))
    y = ys.swapaxes(0, 1).astype(u.dtype) + u * D.astype(u.dtype)
    return y, hT


def mamba_apply(p: dict, x: jax.Array, *, rt: Runtime,
                state: dict | None = None, return_state: bool = False):
    """x: (B, S, D) -> (B, S, D). Train/prefill form (scan over S)."""
    b, s, _ = x.shape
    di, d_state, d_conv, dt_rank = _mamba_dims(p)
    xz = constrain_feature_sharded(dense_apply(p["in_proj"], x, rt), rt)
    u_pre, z = jnp.split(xz, 2, axis=-1)
    u = jax.nn.silu(_causal_depthwise_conv(u_pre, p["conv_w"], p["conv_b"]))
    u = constrain_feature_sharded(u, rt)
    proj = dense_apply(p["x_proj"], u, rt)
    dt, Bm, Cm = _ssm_inputs(p, proj, dt_rank, d_state)
    # dt stays in compute dtype for the full sequence; f32 happens per-chunk
    dt = jax.nn.softplus(dense_apply(p["dt_proj"], dt, rt)
                         .astype(jnp.float32)).astype(x.dtype)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    h0 = (state["h"] if state is not None
          else jnp.zeros((b, di, d_state), jnp.float32))
    y, hT = _selective_scan(u, dt, A, Bm, Cm,
                            p["D"].astype(jnp.float32), h0,
                            unroll=rt.unroll)
    out = dense_apply(p["out_proj"], (y.astype(x.dtype) * jax.nn.silu(z)), rt)
    if return_state:
        new_state = {"h": hT,
                     "conv": jax.lax.dynamic_slice_in_dim(
                         jnp.pad(u_pre, ((0, 0), (d_conv - 1, 0), (0, 0))),
                         s, d_conv - 1, axis=1).astype(x.dtype)}
        return out, new_state
    return out


def mamba_decode_step(p: dict, x: jax.Array, state: dict, *, rt: Runtime):
    """x: (B, 1, D); state: {'h': (B,di,ds), 'conv': (B,dc-1,di)}."""
    b = x.shape[0]
    di, d_state, d_conv, dt_rank = _mamba_dims(p)
    xz = dense_apply(p["in_proj"], x, rt)
    u, z = jnp.split(xz, 2, axis=-1)                       # (B,1,di)
    window = jnp.concatenate([state["conv"], u.astype(state["conv"].dtype)],
                             axis=1)                       # (B,dc,di)
    u_c = jnp.einsum("bcd,cd->bd", window.astype(jnp.float32),
                     p["conv_w"].astype(jnp.float32)) + p["conv_b"]
    u_c = jax.nn.silu(u_c)[:, None, :]                     # (B,1,di)
    proj = dense_apply(p["x_proj"], u_c.astype(x.dtype), rt)
    dt, Bm, Cm = _ssm_inputs(p, proj, dt_rank, d_state)
    dt = jax.nn.softplus(dense_apply(p["dt_proj"], dt, rt).astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dA = jnp.exp(dt[:, 0, :, None] * A)                    # (B,di,ds)
    dBu = dt[:, 0, :, None] * Bm[:, 0, None, :] * u_c[:, 0, :, None]
    h = dA * state["h"] + dBu
    y = jnp.einsum("bds,bs->bd", h, Cm[:, 0].astype(jnp.float32))
    y = y + u_c[:, 0].astype(jnp.float32) * p["D"]
    out = dense_apply(p["out_proj"],
                      (y[:, None, :].astype(x.dtype) * jax.nn.silu(z)), rt)
    new_state = {"h": h, "conv": window[:, 1:, :]}
    return out, new_state


def _paged_conv(state_conv, u_pre, n_valid, conv_w, conv_b):
    """Causal depthwise conv over a ragged C-token chunk, continuing from
    a cached left-context window (the slab ``conv`` leaf).

    ``state_conv``: (B, dc-1, di) — pre-activations of the last dc-1
    tokens before this chunk; ``u_pre``: (B, C, di); ``n_valid``: (B,)
    int32 in [0, C]. Returns ``(u_c, new_conv)``: f32 conv pre-silu
    outputs for every chunk position (invalid positions produce garbage
    the caller masks/ignores) and the window advanced to end exactly at
    each row's last *valid* token — a row with ``n_valid == 0`` gets its
    window back unchanged."""
    b, c, di = u_pre.shape
    dcm1 = state_conv.shape[1]
    window = jnp.concatenate([state_conv, u_pre.astype(state_conv.dtype)],
                             axis=1)                   # (B, dc-1+C, di)
    w32 = conv_w.astype(jnp.float32)
    win32 = window.astype(jnp.float32)
    out = jnp.zeros((b, c, di), jnp.float32)
    for j in range(dcm1 + 1):   # dc taps (dc is 4): unrolled, no While
        out = out + win32[:, j:j + c, :] * w32[j]
    out = out + conv_b.astype(jnp.float32)
    idx = n_valid[:, None].astype(jnp.int32) \
        + jnp.arange(dcm1, dtype=jnp.int32)[None, :]   # (B, dc-1)
    new_conv = jnp.take_along_axis(window, idx[..., None], axis=1)
    return out, new_conv


def mamba_paged_step(p: dict, x: jax.Array, state: dict, n_valid, *,
                     rt: Runtime):
    """Slab-backed ragged chunk step: x (B, C, D) with ``n_valid`` (B,)
    valid tokens per row, state gathered from the StateCache slab region
    ({'h': (B,di,ds) f32, 'conv': (B,dc-1,di)}).

    Invalid positions are identity-masked (dt forced to 0 => dA = 1,
    dBu = 0), so the returned state equals running only each row's valid
    prefix — a fully inactive row (n_valid == 0) returns its state bit
    exact. Chaining C=1 steps matches ``mamba_decode_step`` and the
    ``mamba_apply`` full scan (regression-tested). The chunk scans one
    position at a time (``_sequential_scan``), so a full batch of
    published-width slabs needs no (B, C, di, ds) temporaries."""
    b, c, _ = x.shape
    di, d_state, d_conv, dt_rank = _mamba_dims(p)
    xz = dense_apply(p["in_proj"], x, rt)
    u_pre, z = jnp.split(xz, 2, axis=-1)                   # (B,C,di)
    u_c, new_conv = _paged_conv(state["conv"], u_pre, n_valid,
                                p["conv_w"], p["conv_b"])
    u = jax.nn.silu(u_c).astype(x.dtype)                   # (B,C,di)
    proj = dense_apply(p["x_proj"], u, rt)
    dt, Bm, Cm = _ssm_inputs(p, proj, dt_rank, d_state)
    dt = jax.nn.softplus(dense_apply(p["dt_proj"], dt, rt)
                         .astype(jnp.float32)).astype(x.dtype)
    valid = jnp.arange(c, dtype=jnp.int32)[None, :] \
        < n_valid[:, None].astype(jnp.int32)               # (B,C)
    dt = jnp.where(valid[..., None], dt, jnp.zeros_like(dt))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    y, hT = _sequential_scan(u, dt, A, Bm, Cm, p["D"].astype(jnp.float32),
                             state["h"])
    out = dense_apply(p["out_proj"], (y.astype(x.dtype) * jax.nn.silu(z)),
                      rt)
    return out, {"h": hT, "conv": new_conv}


# ===========================================================================
# mLSTM (xLSTM's matrix-memory block, stabilized exponential gating)
# ===========================================================================

def mlstm_init(key, d_model: int, *, n_heads: int = 4, expand: int = 2,
               d_conv: int = 4, dtype=jnp.float32) -> dict:
    di = expand * d_model
    ks = jax.random.split(key, 7)
    s = di ** -0.5
    return {
        "in_proj": dense_init(ks[0], d_model, 2 * di, dtype=dtype),
        "conv_w": jax.random.normal(ks[1], (d_conv, di), dtype) * 0.2,
        "conv_b": jnp.zeros((di,), dtype),
        "wq": dense_init(ks[2], di, di, dtype=dtype),
        "wk": dense_init(ks[3], di, di, dtype=dtype),
        "wv": dense_init(ks[4], di, di, dtype=dtype),
        "w_gates": dense_init(ks[5], di, 2 * n_heads, dtype=dtype),
        "out_norm_g": jnp.ones((di,), dtype),
        "down_proj": dense_init(ks[6], di, d_model, dtype=dtype),
    }


def _mlstm_dims(p, n_heads):
    w = p["wq"]["w"]
    di = w.logical_shape[0] if hasattr(w, "logical_shape") else w.shape[0]
    return di, n_heads, di // n_heads


def mlstm_init_state(p, batch: int, dtype=jnp.float32, *, n_heads: int = 4):
    di, nh, dh = _mlstm_dims(p, n_heads)
    d_conv = p["conv_w"].shape[0]
    return {"C": jnp.zeros((batch, nh, dh, dh), jnp.float32),
            "n": jnp.zeros((batch, nh, dh), jnp.float32),
            "m": jnp.zeros((batch, nh), jnp.float32),
            "conv": jnp.zeros((batch, d_conv - 1, di), dtype)}


def _mlstm_qkv_gates(p, u, rt, n_heads):
    """u: (B,S,di) -> q,k,v (B,S,NH,dh), i/f gate preacts (B,S,NH)."""
    b, s, di = u.shape
    _, nh, dh = _mlstm_dims(p, n_heads)
    q = dense_apply(p["wq"], u, rt).reshape(b, s, nh, dh)
    k = dense_apply(p["wk"], u, rt).reshape(b, s, nh, dh) * (dh ** -0.5)
    v = dense_apply(p["wv"], u, rt).reshape(b, s, nh, dh)
    gates = dense_apply(p["w_gates"], u, rt).astype(jnp.float32)
    ig, fg = jnp.split(gates, 2, axis=-1)                  # (B,S,NH)
    return q, k, v, ig, fg


def _mlstm_cell(C, n, m, q, k, v, ig, fg):
    """Single stabilized mLSTM step. C:(B,NH,dh,dh) n:(B,NH,dh) m:(B,NH);
    q,k,v:(B,NH,dh); ig,fg:(B,NH)."""
    logf = jax.nn.log_sigmoid(fg)
    m_new = jnp.maximum(logf + m, ig)
    fs = jnp.exp(logf + m - m_new)[..., None]              # (B,NH,1)
    is_ = jnp.exp(ig - m_new)[..., None]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    C = fs[..., None] * C + is_[..., None] * vf[..., :, None] * kf[..., None, :]
    n = fs * n + is_ * kf
    qf = q.astype(jnp.float32)
    num = jnp.einsum("bhvk,bhk->bhv", C, qf)
    den = jnp.abs(jnp.einsum("bhk,bhk->bh", n, qf))
    den = jnp.maximum(den, jnp.exp(-m_new))[..., None]
    return C, n, m_new, num / den


def _mlstm_chunkwise(q, k, v, ig, fg, C0, n0, m0, *, chunk: int = 128,
                     unroll: bool = False):
    """Chunkwise-parallel stabilized mLSTM (linear-attention form).

    q,k,v: (B,S,NH,dh); ig,fg: (B,S,NH); states C0 (B,NH,dh,dh),
    n0 (B,NH,dh), m0 (B,NH). Returns (h (B,S,NH,dh), C, n, m).

    Per-step recurrence (see _mlstm_cell) unrolls within a chunk to
      m_t = F_t + max(m0, G_t),  F_t = cumsum(logf),  G_t = cummax(logi-F)
      h_num_t = e^{F_t+m0-m_t} C0 q_t + sum_j [e^{logi_j-F_j+F_t-m_t}
                                               (q_t.k_j)] v_j   (j<=t)
    so a chunk costs one (c, c) masked score matrix per head — the
    (B,S,NH,dh,dh) per-step state tensor never exists. The chunk boundary
    state update is one einsum. Chunk bodies are remat'd: saved residuals
    are nc matrix states instead of S of them."""
    b, s, nh, dh = q.shape
    c = min(chunk, s)
    if s % c:
        c = s
    nc = s // c

    def cx(t):  # (B,S,...) -> (nc, B, c, ...)
        return t.reshape(b, nc, c, *t.shape[2:]).swapaxes(0, 1)

    xs = tuple(map(cx, (q, k, v, ig.astype(jnp.float32),
                        fg.astype(jnp.float32))))

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def chunk_body(carry, inp):
        inp = opt_barrier(inp)
        C0c, n0c, m0c = carry                    # (B,NH,dh,dh),(B,NH,dh),(B,NH)
        q_c, k_c, v_c, ig_c, fg_c = inp          # (B,c,NH,*)
        logf = jax.nn.log_sigmoid(fg_c)          # (B,c,NH)
        F = jnp.cumsum(logf, axis=1)             # inclusive
        G = jax.lax.cummax(ig_c - F, axis=1)
        m = F + jnp.maximum(m0c[:, None], G)     # (B,c,NH)
        qf = q_c.astype(jnp.float32)
        kf = k_c.astype(jnp.float32)
        vf = v_c.astype(jnp.float32)
        # inter-chunk: e^{F+m0-m} (C0 q_t), (n0.q_t)
        scale0 = jnp.exp(F + m0c[:, None] - m)   # (B,c,NH)
        num0 = jnp.einsum("bhvk,bchk->bchv", C0c, qf) * scale0[..., None]
        den0 = jnp.einsum("bhk,bchk->bch", n0c, qf) * scale0
        # intra-chunk scores: w_tj = e^{logi_j - F_j + F_t - m_t}, j<=t.
        # Mask the exponent (not the result): for j>t it grows like
        # F_t - F_j ~ 0.7*(j-t), which overflows exp at c>=128 and would
        # turn the masked product into inf*0 = NaN.
        a_j = (ig_c - F)                          # (B,c,NH) at index j
        expo = a_j[:, None, :, :] + (F - m)[:, :, None, :]        # (B,t,j,NH)
        causal = jnp.tril(jnp.ones((c, c), jnp.bool_))
        w = jnp.exp(jnp.where(causal[None, :, :, None], expo, -jnp.inf))
        s_qk = jnp.einsum("bthk,bjhk->btjh", qf, kf)
        sw = s_qk * w
        num = num0 + jnp.einsum("btjh,bjhv->bthv", sw, vf)
        # n_t.q_t = (n0.q_t) e^{...} + sum_j w_tj (k_j.q_t) = den0 + sum_j sw
        den = den0 + jnp.sum(sw, axis=2)
        h = num / jnp.maximum(jnp.abs(den)[..., None], jnp.exp(-m)[..., None])
        # chunk-end state
        F_c = F[:, -1]                            # (B,NH)
        m_c = m[:, -1]
        sc_state = jnp.exp(ig_c - F + F_c[:, None] - m_c[:, None])  # (B,c,NH)
        C = jnp.exp(F_c + m0c - m_c)[..., None, None] * C0c \
            + jnp.einsum("bch,bchv,bchk->bhvk", sc_state, vf, kf)
        n = jnp.exp(F_c + m0c - m_c)[..., None] * n0c \
            + jnp.einsum("bch,bchk->bhk", sc_state, kf)
        return (C, n, m_c), h

    (C, n, m), hs = jax.lax.scan(chunk_body, (C0, n0, m0), xs,
                                 unroll=True if unroll else 1)
    h = hs.swapaxes(0, 1).reshape(b, s, nh, dh)
    return h, C, n, m


def mlstm_apply(p: dict, x: jax.Array, *, rt: Runtime, n_heads: int = 4,
                state: dict | None = None, return_state: bool = False):
    """x: (B, S, D) -> (B, S, D)."""
    b, s, _ = x.shape
    di, nh, dh = _mlstm_dims(p, n_heads)
    xz = constrain_feature_sharded(dense_apply(p["in_proj"], x, rt), rt)
    u_pre, z = jnp.split(xz, 2, axis=-1)
    u = jax.nn.silu(_causal_depthwise_conv(u_pre, p["conv_w"], p["conv_b"]))
    u = constrain_feature_sharded(u, rt)
    q, k, v, ig, fg = _mlstm_qkv_gates(p, u, rt, nh)
    st = state or mlstm_init_state(p, b, x.dtype, n_heads=nh)

    hs4, C, n, m = _mlstm_chunkwise(q, k, v, ig, fg, st["C"], st["n"],
                                    st["m"], unroll=rt.unroll)
    h = hs4.reshape(b, s, di).astype(x.dtype)
    # per-head groupnorm-ish output norm (rms over head dim)
    hn = h.reshape(b, s, nh, dh)
    hn = hn * jax.lax.rsqrt(jnp.mean(hn.astype(jnp.float32) ** 2, axis=-1,
                                     keepdims=True) + 1e-6).astype(x.dtype)
    h = hn.reshape(b, s, di) * p["out_norm_g"].astype(x.dtype)
    out = dense_apply(p["down_proj"], h * jax.nn.silu(z), rt)
    if return_state:
        d_conv = p["conv_w"].shape[0]
        conv = jax.lax.dynamic_slice_in_dim(
            jnp.pad(u_pre, ((0, 0), (d_conv - 1, 0), (0, 0))), s, d_conv - 1,
            axis=1).astype(x.dtype)
        return out, {"C": C, "n": n, "m": m, "conv": conv}
    return out


def mlstm_decode_step(p: dict, x: jax.Array, state: dict, *, rt: Runtime,
                      n_heads: int = 4):
    b = x.shape[0]
    di, nh, dh = _mlstm_dims(p, n_heads)
    xz = dense_apply(p["in_proj"], x, rt)
    u, z = jnp.split(xz, 2, axis=-1)                       # (B,1,di)
    window = jnp.concatenate([state["conv"], u.astype(state["conv"].dtype)],
                             axis=1)
    u_c = jnp.einsum("bcd,cd->bd", window.astype(jnp.float32),
                     p["conv_w"].astype(jnp.float32)) + p["conv_b"]
    u_c = jax.nn.silu(u_c)[:, None, :].astype(x.dtype)
    q, k, v, ig, fg = _mlstm_qkv_gates(p, u_c, rt, nh)
    C, n, m, h = _mlstm_cell(state["C"], state["n"], state["m"],
                             q[:, 0], k[:, 0], v[:, 0], ig[:, 0], fg[:, 0])
    h = h.reshape(b, 1, di).astype(x.dtype)
    hn = h.reshape(b, 1, nh, dh)
    hn = hn * jax.lax.rsqrt(jnp.mean(hn.astype(jnp.float32) ** 2, axis=-1,
                                     keepdims=True) + 1e-6).astype(x.dtype)
    h = hn.reshape(b, 1, di) * p["out_norm_g"].astype(x.dtype)
    out = dense_apply(p["down_proj"], h * jax.nn.silu(z), rt)
    return out, {"C": C, "n": n, "m": m, "conv": window[:, 1:, :]}


def mlstm_paged_step(p: dict, x: jax.Array, state: dict, n_valid, *,
                     rt: Runtime, n_heads: int = 4):
    """Slab-backed ragged chunk step for mLSTM: x (B, C, D), ``n_valid``
    (B,) valid tokens per row, state from the slab region.

    Invalid positions are identity-masked through the gates: fg forced to
    +1e9 (log_sigmoid -> exactly 0.0 in f32, decay 1) and ig to -1e30
    (zero contribution), so ``_mlstm_chunkwise`` carries (C, n, m) across
    them untouched and each row's returned state equals running only its
    valid prefix."""
    b, c, _ = x.shape
    di, nh, dh = _mlstm_dims(p, n_heads)
    xz = dense_apply(p["in_proj"], x, rt)
    u_pre, z = jnp.split(xz, 2, axis=-1)                   # (B,C,di)
    u_c, new_conv = _paged_conv(state["conv"], u_pre, n_valid,
                                p["conv_w"], p["conv_b"])
    u = jax.nn.silu(u_c).astype(x.dtype)
    q, k, v, ig, fg = _mlstm_qkv_gates(p, u, rt, nh)       # gates (B,C,NH)
    valid = (jnp.arange(c, dtype=jnp.int32)[None, :]
             < n_valid[:, None].astype(jnp.int32))[..., None]
    fg = jnp.where(valid, fg, jnp.float32(1e9))
    ig = jnp.where(valid, ig, jnp.float32(-1e30))
    h4, C_, n_, m_ = _mlstm_chunkwise(q, k, v, ig, fg, state["C"],
                                      state["n"], state["m"],
                                      unroll=rt.unroll)
    h = h4.reshape(b, c, di).astype(x.dtype)
    hn = h.reshape(b, c, nh, dh)
    hn = hn * jax.lax.rsqrt(jnp.mean(hn.astype(jnp.float32) ** 2, axis=-1,
                                     keepdims=True) + 1e-6).astype(x.dtype)
    h = hn.reshape(b, c, di) * p["out_norm_g"].astype(x.dtype)
    out = dense_apply(p["down_proj"], h * jax.nn.silu(z), rt)
    return out, {"C": C_, "n": n_, "m": m_, "conv": new_conv}


# ===========================================================================
# sLSTM (scalar-memory xLSTM block, block-diagonal recurrence)
# ===========================================================================

def slstm_init(key, d_model: int, *, n_heads: int = 4, dtype=jnp.float32) -> dict:
    dh = d_model // n_heads
    ks = jax.random.split(key, 3)
    return {
        # input projections for (z, i, f, o) stacked: (D, 4D)
        "w_in": dense_init(ks[0], d_model, 4 * d_model, dtype=dtype),
        # block-diagonal recurrent weights per gate: (4, NH, dh, dh)
        "r": jax.random.normal(ks[1], (4, n_heads, dh, dh), dtype)
             * (dh ** -0.5),
        "out_proj": dense_init(ks[2], d_model, d_model, dtype=dtype),
    }


def slstm_init_state(p, batch: int, dtype=jnp.float32):
    four, nh, dh, _ = p["r"].shape
    return {k: jnp.zeros((batch, nh, dh), jnp.float32)
            for k in ("c", "n", "h")} | \
           {"m": jnp.zeros((batch, nh, dh), jnp.float32)}


def _slstm_cell(p, carry, x_t):
    """x_t: (B, 4D) preactivations from input; carry dicts (B,NH,dh)."""
    four, nh, dh = p["r"].shape[0], p["r"].shape[1], p["r"].shape[2]
    b = x_t.shape[0]
    c, n, m, h = carry["c"], carry["n"], carry["m"], carry["h"]
    rec = jnp.einsum("ghij,bhj->bghi", p["r"].astype(jnp.float32), h)
    pre = x_t.reshape(b, 4, nh, dh).astype(jnp.float32) + rec
    z_t = jnp.tanh(pre[:, 0])
    i_t = pre[:, 1]
    f_t = pre[:, 2]
    o_t = jax.nn.sigmoid(pre[:, 3])
    logf = jax.nn.log_sigmoid(f_t)
    m_new = jnp.maximum(logf + m, i_t)
    fs = jnp.exp(logf + m - m_new)
    is_ = jnp.exp(i_t - m_new)
    c = fs * c + is_ * z_t
    n = fs * n + is_
    h_new = o_t * c / jnp.maximum(n, 1e-6)
    return {"c": c, "n": n, "m": m_new, "h": h_new}


def slstm_apply(p: dict, x: jax.Array, *, rt: Runtime,
                state: dict | None = None, return_state: bool = False):
    b, s, d = x.shape
    nh, dh = p["r"].shape[1], p["r"].shape[2]
    pre = dense_apply(p["w_in"], x, rt)                    # (B,S,4D)
    st = state or slstm_init_state(p, b, x.dtype)

    def step(carry, x_t):
        carry = _slstm_cell(p, carry, x_t)
        return carry, carry["h"]

    # (sequential by nature; per-step state ~ (B,NH,dh) — cheap). Not
    # unrolled even for cost variants: 4096 unrolled elementwise steps would
    # explode HLO for <0.5% of layer FLOPs (documented in DESIGN.md §6).
    carry, hs = jax.lax.scan(step, st, jnp.swapaxes(pre, 0, 1))
    h = jnp.swapaxes(hs, 0, 1).reshape(b, s, d).astype(x.dtype)
    out = dense_apply(p["out_proj"], h, rt)
    if return_state:
        return out, carry
    return out


def slstm_decode_step(p: dict, x: jax.Array, state: dict, *, rt: Runtime):
    b, _, d = x.shape
    pre = dense_apply(p["w_in"], x, rt)[:, 0]              # (B,4D)
    carry = _slstm_cell(p, state, pre)
    h = carry["h"].reshape(b, 1, d).astype(x.dtype)
    return dense_apply(p["out_proj"], h, rt), carry


def slstm_paged_step(p: dict, x: jax.Array, state: dict, n_valid, *,
                     rt: Runtime):
    """Slab-backed ragged chunk step for sLSTM: x (B, C, D), ``n_valid``
    (B,) valid tokens per row. The recurrence is inherently sequential, so
    the chunk scans per token with a per-row masked carry: rows past
    their valid length keep the previous state bit exact (the cell still
    computes, the ``where`` discards it)."""
    b, c, d = x.shape
    pre = dense_apply(p["w_in"], x, rt)                    # (B,C,4D)
    valid = jnp.arange(c, dtype=jnp.int32)[None, :] \
        < n_valid[:, None].astype(jnp.int32)               # (B,C)

    def step(carry, inp):
        x_t, v_t = inp                                     # (B,4D), (B,)
        new = _slstm_cell(p, carry, x_t)
        keep = v_t[:, None, None]
        carry = {k: jnp.where(keep, new[k], carry[k]) for k in carry}
        return carry, carry["h"]

    carry, hs = jax.lax.scan(step, state,
                             (jnp.swapaxes(pre, 0, 1),
                              jnp.swapaxes(valid, 0, 1)))
    h = jnp.swapaxes(hs, 0, 1).reshape(b, c, d).astype(x.dtype)
    return dense_apply(p["out_proj"], h, rt), carry
