"""Analytical model of the paper's §3.1 load/compute decoupling, on TPU terms.

The paper's input buffer decouples RAM→buffer loading (clk_inbuff) from PU
compute (clk_compute); the pipeline is sound iff loading stays ahead of
compute. On TPU the same condition governs the Pallas/Mosaic double-buffered
pipeline: for each grid step, the DMA of the *next* (activation, weight-code)
block must finish within the MXU time of the *current* block:

    t_load(block)    = bytes(block) / BW_hbm
    t_compute(block) = flops(block) / peak_flops

This module evaluates that inequality for candidate BlockSpec shapes and is
used (a) by the kernels to choose default block shapes, (b) by the benchmark
harness to report the "pipeline feasibility" margin the paper argues in prose
(300 ns load vs 500 ns compute → compute-bound, pipeline hides the load).

Quantization enters t_load directly: b-bit SPx codes shrink the weight-block
bytes by 16/b versus bf16, widening the pipeline margin — this is the paper's
two contributions composing.
"""
from __future__ import annotations

import dataclasses
import math

__all__ = ["HwSpec", "TPU_V5E", "HARDWARE", "hw_for_kind", "current_hw",
           "BlockPlan", "plan_matmul_blocks"]


@dataclasses.dataclass(frozen=True)
class HwSpec:
    name: str
    peak_bf16_flops: float      # FLOP/s per chip
    hbm_bw: float               # bytes/s per chip
    ici_bw: float               # bytes/s per link
    vmem_bytes: int             # VMEM one kernel may claim (scoped limit)
    mxu_dim: int = 128          # systolic tile
    source: str = ""


#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
#: 819 GB/s, 1,600 Gbit/s ICI per chip (4 links of 50 GB/s). The chip has
#: 128 MiB of VMEM, but Mosaic gives one kernel a 16 MiB scoped window
#: unless the kernel raises ``vmem_limit_bytes`` — the planner budgets
#: against that window, which is what the compiler accepts as-is.
TPU_V5E = HwSpec(
    name="tpu_v5e",
    peak_bf16_flops=197e12,
    hbm_bw=819e9,
    ici_bw=50e9,
    vmem_bytes=16 * 1024 * 1024,
    mxu_dim=128,
    source="Google Cloud documentation, 'TPU v5e'",
)

#: the one hardware table, keyed by ``jax.Device.device_kind``
HARDWARE = {"TPU v5 lite": TPU_V5E}


def hw_for_kind(device_kind: str) -> HwSpec:
    """The table entry for ``device_kind``; an unknown chip is an error,
    never a default."""
    try:
        return HARDWARE[device_kind]
    except KeyError:
        raise LookupError(
            f"no hardware entry for device kind {device_kind!r}; known: "
            f"{sorted(HARDWARE)}") from None


def current_hw() -> HwSpec:
    """The running chip's entry on TPU; off the chip (CPU tests,
    interpret-mode kernels, described-topology compiles) plans target
    v5e explicitly."""
    import jax
    if jax.default_backend() == "tpu":
        return hw_for_kind(jax.devices()[0].device_kind)
    return TPU_V5E


@dataclasses.dataclass
class BlockPlan:
    bm: int
    bn: int
    bk: int
    weight_bits: int
    vmem_bytes: int             # working set incl. double buffers + acc
    t_load: float               # s, per grid step (next-block DMA)
    t_compute: float            # s, per grid step (MXU on current block)
    pipelined: bool             # t_load <= t_compute (paper's condition)
    arithmetic_intensity: float # flops / HBM byte for the whole matmul

    @property
    def margin(self) -> float:
        """compute/load ratio; >1 means the DMA is fully hidden."""
        return self.t_compute / max(self.t_load, 1e-30)


def _block_cost(m, n, k, bm, bn, bk, weight_bits, act_bytes, hw: HwSpec):
    # Per grid step we stream one activation tile (bm x bk) and one weight
    # tile (bk x bn) at `weight_bits`; the f32 accumulator (bm x bn) lives in
    # VMEM across the k-loop (written back once per (m, n) tile).
    load_bytes = bm * bk * act_bytes + bk * bn * weight_bits / 8
    flops = 2.0 * bm * bn * bk
    t_load = load_bytes / hw.hbm_bw
    t_compute = flops / hw.peak_bf16_flops
    # double-buffered input and output tiles, the f32 accumulator, and the
    # in-VMEM decode of one weight tile: int32 codes, f32 levels and the
    # MXU operand in the activation dtype (kernels/spx_matmul.py)
    vmem = (2 * (bm * bk * act_bytes + bk * bn * weight_bits / 8)
            + 2 * bm * bn * act_bytes + bm * bn * 4
            + bk * bn * (4 + 4 + act_bytes))
    return load_bytes, flops, t_load, t_compute, int(vmem)


def plan_matmul_blocks(m: int, n: int, k: int, *, weight_bits: int = 16,
                       act_bytes: int = 2, hw: HwSpec = TPU_V5E,
                       candidates=(128, 256, 512, 1024, 2048),
                       candidates_m=None, candidates_n=None,
                       candidates_k=None,
                       vmem_fraction: float = 0.9) -> BlockPlan:
    """Pick (bm, bn, bk) maximizing pipeline margin subject to VMEM fit and
    MXU alignment. Deterministic, pure math — used by the block planner
    (repro.runtime.planner) and reported in the benchmarks.

    ``candidates_m/n/k`` restrict the search per dimension (the planner
    passes divisor-filtered lists so chosen blocks tile the problem
    exactly); each defaults to ``candidates``.
    """
    best = None
    for bm in (candidates_m if candidates_m is not None else candidates):
        if bm > max(m, hw.mxu_dim):
            continue
        for bn in (candidates_n if candidates_n is not None else candidates):
            if bn > max(n, hw.mxu_dim):
                continue
            for bk in (candidates_k if candidates_k is not None
                       else candidates):
                if bk > max(k, hw.mxu_dim):
                    continue
                load_b, flops, t_l, t_c, vmem = _block_cost(
                    m, n, k, bm, bn, bk, weight_bits, act_bytes, hw)
                if vmem > hw.vmem_bytes * vmem_fraction:
                    continue
                # whole-matmul arithmetic intensity at this blocking: the
                # activation tile re-streams once per n-block, weights once
                # per m-block.
                n_m, n_n, n_k = (math.ceil(m / bm), math.ceil(n / bn),
                                 math.ceil(k / bk))
                total_bytes = (n_n * m * k * act_bytes
                               + n_m * k * n * weight_bits / 8
                               + m * n * act_bytes)
                ai = (2.0 * m * n * k) / total_bytes
                plan = BlockPlan(bm, bn, bk, weight_bits, vmem, t_l, t_c,
                                 t_l <= t_c, ai)
                key = (plan.pipelined, plan.margin, -vmem)
                if best is None or key > (best.pipelined, best.margin,
                                          -best.vmem_bytes):
                    best = plan
    if best is None:  # tiny problem: single MXU tile
        load_b, flops, t_l, t_c, vmem = _block_cost(
            m, n, k, hw.mxu_dim, hw.mxu_dim, hw.mxu_dim, weight_bits,
            act_bytes, hw)
        best = BlockPlan(hw.mxu_dim, hw.mxu_dim, hw.mxu_dim, weight_bits,
                         vmem, t_l, t_c, t_l <= t_c, 2.0 * hw.mxu_dim / 3)
    return best
