"""Pallas TPU kernel: pipelined SPx-quantized matmul (the paper's §3.1+§3.2).

This is the paper's accelerator, re-thought for the TPU memory hierarchy:

  FPGA                         TPU (this kernel)
  ----                         -----------------
  RAM -> input buffer          HBM -> VMEM tiles, double-buffered by the
  (clk_inbuff)                 Mosaic pipeline across grid steps
  PU pipeline (clk_compute)    MXU consuming the current VMEM tile while the
                               next tile's DMA is in flight
  row-of-weights per clock     (bk x bn) weight-code tile per grid step
  shift-add of PoT terms       b-bit code -> level by a lane gather from
                               the VMEM codebook (8-bit codes) or a select
                               chain over it in SMEM (int4), on the VPU,
                               then a dense MXU matmul
  temporary `array t`          f32 accumulator tile in VMEM scratch

The load/compute decoupling argument of §3.1 (loading must stay ahead of
compute) is exactly the Pallas pipelining condition; quantized weight tiles
shrink t_load by 16/b versus bf16, which is what makes the pipeline
compute-bound for realistic (bm, bn, bk) — see core/pipeline.py for the
analytical check and the benchmarks for numbers.

Grid layout: (M/bm, N/bn, K/bk), K innermost; the output BlockSpec ignores
the K index so the same (bm, bn) accumulator tile is revisited across the
K loop (standard Pallas accumulation idiom).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.spx import int4_group

from .decode import lut_decode, lut_operand, unpack_int4_tile

__all__ = ["spx_matmul_pallas"]


def _kernel(x_ref, codes_ref, scale_ref, lut_ref, o_ref, acc_ref, *,
            group: int | None, n_k: int, out_dtype):
    """One grid step: decode a weight tile in VMEM, MXU-accumulate.
    ``group`` is the int4 lane-group width of packed codes (None when the
    codes are stored one per byte)."""
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if group is None:
        codes = codes_ref[...].astype(jnp.int32)
    else:
        codes = unpack_int4_tile(codes_ref[...], group)
    # LUT decode on the VPU against the resident codebook
    w = lut_decode(codes, lut_ref)
    x = x_ref[...]
    acc_ref[...] += jax.lax.dot_general(
        x, w.astype(x.dtype),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(k_idx == n_k - 1)
    def _finalize():
        # per-output-channel alpha applied once, after accumulation
        o_ref[...] = (acc_ref[...] * scale_ref[...]).astype(out_dtype)


@functools.partial(
    jax.jit,
    static_argnames=("packed", "bm", "bn", "bk", "out_dtype", "interpret"))
def spx_matmul_pallas(x, codes, scale, lut, *, packed: bool,
                      bm: int, bn: int, bk: int, out_dtype=None,
                      interpret: bool = False):
    """x:(M,K) @ dequant(codes:(K,N), scale:(1,N), lut:(2^b,)) -> (M,N).

    codes are uint8; if ``packed`` the stored array is (K, N//2) with two
    4-bit codes per byte in the lane-grouped layout of
    ``core/spx.pack_int4`` (``bn`` is then a multiple of twice the group
    width). Block shapes are chosen by the planner
    (repro.runtime.planner) and passed explicitly; shapes must be pre-padded
    to block multiples by the ops.py wrapper.
    """
    m, k = x.shape
    n = scale.shape[-1]
    out_dtype = out_dtype or x.dtype
    bm, bn, bk = min(bm, m), min(bn, n), min(bk, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, n, k, bm, bn, bk)
    n_k = k // bk
    pack_div = 2 if packed else 1
    group = int4_group(n) if packed else None
    assert group is None or bn % (2 * group) == 0, (n, bn, group)

    grid = (m // bm, n // bn, n_k)
    lut, lut_spec = lut_operand(lut, bn, "spx_matmul")
    return pl.pallas_call(
        functools.partial(_kernel, group=group, n_k=n_k,
                          out_dtype=out_dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn // pack_div), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
            lut_spec,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, codes, scale, lut)
