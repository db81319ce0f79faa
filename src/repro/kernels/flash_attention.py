"""Pallas TPU kernel: online-softmax (flash) attention for prefill.

Attention is the other hot matmul pair in every assigned transformer; the
same §3.1 pipelining story applies: K/V tiles stream HBM->VMEM while the MXU
works on the current block, and the softmax statistics (running max m,
running denominator l) live in VMEM scratch — the paper's `array t` again.

Grid: (B*H, Sq/bq, Skv/bkv), KV innermost. Causal masking prunes nothing
structurally (blocks are still visited) but masks within the tile; the ops.py
wrapper carries the exact sub-quadratic chunked reference used on CPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


__all__ = ["flash_attention_pallas"]

_NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, bq: int, bkv: int, n_kv: int,
            out_dtype):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                       # (bq, dh)
    k = k_ref[0]                       # (bkv, dh)
    v = v_ref[0]                       # (bkv, dh)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
        k_pos = ki * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG_INF)

    m_prev = m_ref[...]                # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)             # (bq, bkv)
    corr = jnp.exp(m_prev - m_new)     # (bq, 1)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        # rows with no unmasked key (can't happen for causal qpos>=0) guard
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "bq", "bkv", "out_dtype", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           bq: int, bkv: int,
                           out_dtype=None, interpret: bool = False):
    """q: (BH, Sq, dh); k, v: (BH, Skv, dh) — heads pre-flattened into the
    leading dim (GQA expansion handled by the wrapper). Block shapes come
    from the planner (repro.runtime.planner). Returns (BH, Sq, dh).
    """
    bh, sq, dh = q.shape
    _, skv, _ = k.shape
    out_dtype = out_dtype or q.dtype
    bq = min(bq, sq)
    bkv = min(bkv, skv)
    assert sq % bq == 0 and skv % bkv == 0, (sq, skv, bq, bkv)
    n_kv = skv // bkv
    scale = 1.0 / (dh ** 0.5)

    grid = (bh, sq // bq, n_kv)
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, bq=bq,
                          bkv=bkv, n_kv=n_kv, out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bkv, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dh), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
