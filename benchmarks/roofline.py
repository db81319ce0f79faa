"""Roofline analysis (deliverable g): per (arch x shape), derive the three
terms from compiled artifacts on the single-pod production mesh:

    compute term    = HLO_FLOPs / peak_FLOP/s          (per chip)
    memory term     = HLO_bytes / HBM_bw               (per chip)
    collective term = collective_bytes / link_bw       (per chip)

XLA's cost analysis counts While bodies once, so raw numbers from the real
(scanned, chunked) step undercount by the layer count. Methodology
(DESIGN.md §6): compile two UNROLLED cost variants of the same step with
1 and 2 layer-periods (inner chunk scans unrolled too — the algorithm is
unchanged, only the While loops disappear), then

    total = cost(P=1) + (n_periods - 1) * (cost(P=2) - cost(P=1)).

Collective bytes are parsed from the partitioned HLO of the same variants
(operand bytes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute), so they get the identical correction for free.

  PYTHONPATH=src python -m benchmarks.roofline --arch granite-3-8b \
      --shape train_4k
  PYTHONPATH=src python -m benchmarks.roofline --all
"""

import argparse
import dataclasses
import json
import os
import re
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402

from repro.configs import assigned_archs, get_config  # noqa: E402
from repro.configs.base import LM_SHAPES  # noqa: E402
from repro.core.pipeline import TPU_V5E  # noqa: E402
from repro.launch.dryrun import parse_collectives  # noqa: E402
from repro.launch.hostdev import set_host_device_count  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.steps import build_step  # noqa: E402

#: chips of the single-pod production mesh (launch/mesh.py, 16 x 16)
CHIPS_SINGLE_POD = 256

ART = os.path.join(os.path.dirname(__file__), "artifacts")

#: fused-decode bench artifact (repo root, like BENCH_serving.json)
BENCH = os.path.normpath(os.path.join(os.path.dirname(__file__), "..",
                                      "BENCH_roofline.json"))


def _compile_cost_variant(cfg, shape, n_periods: int, mesh, *,
                          fsdp: bool, optimizer: str | None,
                          quantized: bool = True, kv_quant: bool = False):
    vcfg = dataclasses.replace(
        cfg, n_layers=len(cfg.pattern) * n_periods,
        n_enc_layers=n_periods if cfg.enc_dec else cfg.n_enc_layers)
    kw: dict = {"unroll": True}
    if shape.kind == "train":
        kw["optimizer"] = optimizer
    else:
        kw["quantized"] = quantized
        if shape.kind == "decode":
            kw["kv_quant"] = kv_quant
    with jax.set_mesh(mesh):
        bundle = build_step(vcfg, shape, mesh, **kw)
        jfn = jax.jit(bundle.fn, in_shardings=bundle.in_shardings,
                      out_shardings=bundle.out_shardings,
                      donate_argnums=bundle.donate_argnums)
        compiled = jfn.lower(*bundle.args).compile()
    cost = compiled.cost_analysis()
    coll = parse_collectives(compiled.as_text())
    coll_bytes = sum(c["bytes"] for c in coll["computations"].values())
    n_while = len(coll["whiles"])
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll_bytes": coll_bytes,
            "n_while": n_while}


def analytic_hbm_bytes(cfg, shape, parallelism: str, quantized: bool,
                       kv_quant: bool = False) -> float:
    """Modeled HBM traffic per chip per step. XLA's 'bytes accessed' is an
    un-fused upper bound (every instruction's operands counted as memory
    traffic), so the roofline's memory term uses this explicit model; the
    raw counter is reported alongside as `hlo_bytes_upper`.

    Terms (all per chip):
      weights: resident shard (tp) or full gathered layers (fsdp), read once
               per pass; 3 passes for train (fwd + remat-recompute + bwd),
               1 for inference. Quantized serving reads b/16 of bf16 bytes.
      activations: ~16 r/w of (tokens_loc x d_model) per layer (QKV/FFN
               inputs+outputs, norms, residuals), bf16.
      kv/state: decode reads the full cache shard once per step; prefill
               writes it once.
      optimizer: sharded moments read+write (train).
    """
    n_chips = CHIPS_SINGLE_POD
    n_model = 16 if parallelism == "tp" else 1
    b, s = shape.global_batch, shape.seq_len
    n = cfg.param_count_estimate()
    n_act = cfg.active_param_count_estimate()
    d = cfg.d_model
    L = cfg.n_layers
    w_bytes = 0.5 if quantized and shape.kind != "train" else 2.0

    if shape.kind == "train":
        tokens_loc = b * s / n_chips if parallelism == "fsdp" \
            else b * s / (n_chips / n_model)
        weights = 3.0 * n_act * 2.0 * (1.0 if parallelism == "fsdp"
                                       else 1.0 / n_model)
        acts = tokens_loc * d * L * 16 * 2.0 * 3 / 2      # fwd+bwd+remat
        opt = 16.0 * n / n_chips                          # moments r/w
        return weights + acts + opt

    # serving: weights shard per chip ("cp" prefill gathers full weights)
    weights = n_act * w_bytes * (1.0 if parallelism == "cp"
                                 else 1.0 / n_model)
    n_attn_layers = sum(1 for p in cfg.pattern
                        if p.split("+")[0] in ("attn", "xdec")) \
        * cfg.n_periods
    # quantized KV: uint8 codes + one f32 scale per (token, head) side
    # (scheme-independent layout — docs/QUANTIZATION.md); else bf16
    from repro.core.spx import kv_token_side_bytes
    kv_elem_bytes = (kv_token_side_bytes(cfg.dh) / cfg.dh if kv_quant
                     else 2.0)
    kv_total = (b * n_attn_layers * cfg.n_kv_heads * s * cfg.dh * 2
                * kv_elem_bytes / n_chips)
    if shape.kind == "decode":
        tokens_loc = b / (n_chips / n_model)
        acts = tokens_loc * d * L * 16 * 2.0
        return weights + kv_total + acts
    tokens_loc = (b * s / n_chips if parallelism == "cp"
                  else b * s / (n_chips / n_model))
    acts = tokens_loc * d * L * 16 * 2.0
    # cp attention reads the gathered K/V per layer
    if parallelism == "cp":
        b_loc = max(b / 16, 1)
        acts += (n_attn_layers * b_loc * cfg.n_kv_heads * s * cfg.dh * 2
                 * 2.0)
    return weights + acts + kv_total


def analytic_collective_bytes(cfg, shape, parallelism: str) -> float:
    """Modeled ICI traffic per chip per step (the parsed HLO numbers carry
    an XLA-CPU artifact: converts fused into collectives upcast bf16
    payloads to f32; reported alongside as `hlo_coll`).

    fsdp train: params gathered once per pass (x2: fwd+bwd-recompute) +
                grads reduce-scattered once: ~3 x 2 x N_active bytes.
    tp train:   per attn/ffn block, SP gather + reduce-scatter of the
                (tokens_loc x d) activation: ~4 x L x tokens x d x 2B.
    tp serving: one all-reduce of (tokens_loc x d) per layer + flash-decode
                LSE merges (tiny).
    """
    n_chips = CHIPS_SINGLE_POD
    b, s = shape.global_batch, shape.seq_len
    d = cfg.d_model
    L = cfg.n_layers
    n_act = cfg.active_param_count_estimate()
    if shape.kind == "train":
        if parallelism == "fsdp":
            return 3.0 * 2.0 * n_act
        tokens_loc = b * s / (n_chips / 16)
        return 4.0 * L * tokens_loc * d * 2.0 + 2.0 * 2.0 * n_act / 16
    if parallelism == "cp":
        # per layer: gathered (quantized) weights + gathered GQA K/V
        n_attn_layers = sum(1 for p in cfg.pattern
                            if p.split("+")[0] in ("attn", "xdec")) \
            * cfg.n_periods
        b_loc = max(b / 16, 1)
        kv_gather = (n_attn_layers * b_loc * cfg.n_kv_heads * s * cfg.dh
                     * 2 * 2.0)
        return n_act * 0.5 + kv_gather
    tokens = (b if shape.kind == "decode" else b * s) / (n_chips / 16)
    return 2.0 * L * tokens * d * 2.0


def analytic_model_flops(cfg, shape) -> float:
    """Useful FLOPs per step, global: 6·N_active·tokens for train,
    2·N_active·tokens for inference, plus causal attention terms."""
    n_act = cfg.active_param_count_estimate()
    b, s = shape.global_batch, shape.seq_len
    n_attn_layers = sum(1 for p in cfg.pattern
                        if p.split("+")[0] in ("attn", "xdec")) \
        * cfg.n_periods
    dh, hq = cfg.dh, cfg.n_heads
    if shape.kind == "train":
        core = 6.0 * n_act * b * s
        attn = 6.0 * n_attn_layers * b * (s * s / 2) * hq * dh * 2
        return core + attn
    if shape.kind == "prefill":
        core = 2.0 * n_act * b * s
        attn = 2.0 * n_attn_layers * b * (s * s / 2) * hq * dh * 2
        return core + attn
    # decode: one token per sequence against an s-deep cache
    core = 2.0 * n_act * b
    attn = 2.0 * n_attn_layers * b * s * hq * dh * 2
    return core + attn


def run_cell(arch: str, shape_name: str, *, quantized: bool = True,
             kv_quant: bool = False, verbose: bool = True) -> dict | None:
    cfg = get_config(arch)
    shape = next(s for s in LM_SHAPES if s.name == shape_name)
    for s, why in cfg.shapes():
        if s.name == shape_name and why:
            return {"arch": arch, "shape": shape_name, "status": why}

    mesh = make_production_mesh(multi_pod=False)
    # policy decisions must come from the FULL config, not the 1-period
    # variant (FSDP / optimizer choice change collectives per layer)
    from repro.sharding import make_policy
    fsdp = make_policy(cfg, mesh).fsdp
    optimizer = ("adamw_q8" if cfg.param_count_estimate() > 30e9
                 else "adamw")

    c1 = _compile_cost_variant(cfg, shape, 1, mesh, fsdp=fsdp,
                               optimizer=optimizer, quantized=quantized,
                               kv_quant=kv_quant)
    c2 = _compile_cost_variant(cfg, shape, 2, mesh, fsdp=fsdp,
                               optimizer=optimizer, quantized=quantized,
                               kv_quant=kv_quant)
    P = cfg.n_periods
    corr = {k: c1[k] + (P - 1) * (c2[k] - c1[k])
            for k in ("flops", "bytes", "coll_bytes")}

    if shape.kind == "train":
        parallelism = ("fsdp" if (cfg.param_count_estimate() <= 30e9
                                  and shape.global_batch % 256 == 0)
                       else "tp")
    elif shape.kind == "prefill" and cfg.param_count_estimate() <= 30e9 \
            and shape.seq_len % 16 == 0 and shape.global_batch % 16 == 0 \
            and not cfg.enc_dec:
        parallelism = "cp"       # context-parallel prefill (§Perf cell 2)
    else:
        parallelism = "tp"
    mem_bytes = analytic_hbm_bytes(cfg, shape, parallelism, quantized,
                                   kv_quant=kv_quant)
    coll_bytes = analytic_collective_bytes(cfg, shape, parallelism)

    t_compute = corr["flops"] / TPU_V5E.peak_bf16_flops
    t_memory = mem_bytes / TPU_V5E.hbm_bw
    t_coll = coll_bytes / TPU_V5E.ici_bw
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())

    model_flops = analytic_model_flops(cfg, shape)
    model_per_chip = model_flops / CHIPS_SINGLE_POD
    hlo_ratio = model_per_chip / max(corr["flops"], 1.0)
    mfu_bound = (model_per_chip / TPU_V5E.peak_bf16_flops) / max(bound, 1e-30)

    result = {
        "arch": arch, "shape": shape_name, "status": "ok",
        "mesh": "16x16", "quantized_serving": quantized,
        "kv_quant": kv_quant,
        "parallelism": parallelism,
        "per_chip": {"flops": corr["flops"],
                     "mem_bytes_model": mem_bytes,
                     "coll_bytes_model": coll_bytes,
                     "hlo_bytes_upper": corr["bytes"],
                     "hlo_coll_parsed": corr["coll_bytes"]},
        "raw_p1": c1, "raw_p2": c2, "n_periods": P,
        "terms_s": terms, "dominant": dominant, "bound_s": bound,
        "model_flops_global": model_flops,
        "model_flops_per_chip": model_per_chip,
        "useful_flops_ratio": hlo_ratio,
        "roofline_fraction": mfu_bound,
        "residual_whiles": c1["n_while"],
    }
    if verbose:
        print(f"[{arch} x {shape_name}] {parallelism} dominant={dominant} "
              f"bound={bound*1e3:.2f}ms "
              f"(c={t_compute*1e3:.2f} m={t_memory*1e3:.2f} "
              f"x={t_coll*1e3:.2f}) useful/HLO={hlo_ratio:.2f} "
              f"roofline_frac={mfu_bound:.2f}")
    os.makedirs(ART, exist_ok=True)
    tag = "" if quantized else "_dense"
    if kv_quant:
        tag += "_kv8"
    fname = f"roofline_{arch.replace('.', '_')}_{shape_name}{tag}.json"
    with open(os.path.join(ART, fname), "w") as f:
        json.dump(result, f, indent=1)
    return result


def fused_decode_bench(csv_rows, *, requests: int = 6, slots: int = 2,
                       max_seq: int = 512, new_tokens: int = 24,
                       spec_k: int = 3, seed: int = 3,
                       out_path: str = BENCH) -> dict:
    """Fused vs unfused decode step on the pinned serving workload:
    the ragged decode megakernel (one attention dispatch per tick, spec
    verify included, in-kernel LUT dequant) against the pre-megakernel
    path (per-call paged-attention kernel for plain ticks + full-width
    page-gather verify for draft ticks).

    Reports, per KV axis (plain f32 pages / SPx codes+scale pages):
      * measured decode throughput (warmup pass pays every compile, then
        reset_metrics + a timed pass — serving_bench's protocol),
      * attention ops traced per decode tick (the trace-time op-call
        counters; the fused path is asserted =1 in tests/test_fused_decode),
      * modeled HBM bytes per decode tick (the gather path reads the full
        block-table width and materializes rep-expanded f32 K/V; the
        megakernel streams only touched pages once and keeps the <=1KiB
        codebook LUT in VMEM),
      * the planner's FusedDecodePlan for the tick's geometry.

    On CPU with the DEFAULT (pinned) workload, asserts greedy outputs are
    bit-identical fused vs unfused and fused throughput >= unfused — the
    megakernel is a dispatch/memory optimization, not a numerics change.
    Writes BENCH_roofline.json at the repo root (run.py + CI upload it).
    """
    import time

    import numpy as np
    from repro.configs import get_config, reduced
    from repro.core.spx import kv_token_side_bytes
    from repro.kernels import ops
    from repro.models import lm as lm_mod
    from repro.runtime import Runtime, planner
    from repro.serving.engine import Request, ServeConfig, ServeEngine

    # serving_bench's pinned geometry: dh=128 keeps the SPx byte ratio
    # representative, vocab=32 keeps greedy argmaxes away from near-ties
    cfg = dataclasses.replace(reduced(get_config("gemma-2b"), vocab=32),
                              head_dim=128)
    rt = Runtime(impl="auto", q_chunk=64)
    params = lm_mod.lm_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    # tiled-motif prompts: the structure prompt-lookup drafting feeds on,
    # so the verify window (the megakernel's q_len > 1 rows) stays hot
    prompts = [np.tile(rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
                       4) for _ in range(requests)]

    w = spec_k + 1
    rep = cfg.n_heads // cfg.n_kv_heads
    n_attn_layers = sum(1 for p in cfg.pattern
                        if p.split("+")[0] in ("attn", "xdec")) \
        * cfg.n_periods
    axes = {"paged": rt,
            "paged-spx": rt.replace(kv_quant=True, kv_scheme="spx_8_x3")}
    pinned = (requests, slots, max_seq, new_tokens, spec_k, seed) \
        == (6, 2, 512, 24, 3, 3)
    result: dict = {"config": {"arch": cfg.name, "requests": requests,
                               "batch_slots": slots, "max_seq": max_seq,
                               "new_tokens": new_tokens, "spec_k": spec_k,
                               "gqa_rep": rep,
                               "n_attn_layers": n_attn_layers}}
    print("\n== decode megakernel: fused vs unfused, plain and SPx KV ==")
    for axis, ert in axes.items():
        outs, mets = {}, {}
        for fused in (True, False):
            eng = ServeEngine(params, cfg,
                              ServeConfig(batch_slots=slots, max_seq=max_seq,
                                          quantize="sp2_4", kv_layout="paged",
                                          spec_decode=True, spec_k=spec_k,
                                          fused_decode=fused),
                              rt=ert)
            ops.reset_op_calls()
            for i, p in enumerate(prompts):        # warmup: pay compiles
                eng.submit(Request(rid=i, prompt=p,
                                   max_new_tokens=new_tokens))
            eng.run()
            # every step is compiled now, so the counters hold ops traced,
            # i.e. attention dispatches per compiled tick (layer-scanned)
            traced = ops.op_calls()
            # best-of-3 measured passes: one pass is ~0.1s on CPU, well
            # inside scheduler noise; max-of-3 is the standard antidote
            m, dt = None, float("inf")
            for _ in range(3):
                eng.reset_metrics()
                t0 = time.monotonic()
                for i, p in enumerate(prompts):
                    eng.submit(Request(rid=i, prompt=p,
                                       max_new_tokens=new_tokens))
                outs[fused] = {r.rid: r.output for r in eng.run()}
                dt = min(dt, time.monotonic() - t0)
                mm = eng.metrics()
                if m is None or mm["tokens_per_s"] > m["tokens_per_s"]:
                    m = mm
            mets[fused] = m
            decode_ops = {k: v for k, v in traced.items()
                          if "paged" in k or "decode" in k}
            ps = m["page_size"]
            tok_bytes = (kv_token_side_bytes(cfg.dh)
                         if ert.kv_quant else 4 * cfg.dh)
            s_max = -(-max_seq // ps) * ps          # block-table width
            ctx_mean = float(np.mean([len(p) for p in prompts])
                             + new_tokens / 2)
            s_touch = -(-int(ctx_mean + w) // ps) * ps
            if fused:
                plan = planner.plan_fused_decode(
                    cfg.dh, rep=rep, w=w, page_size=ps, act_bytes=4,
                    kv_scheme=ert.kv_scheme if ert.kv_quant else None)
                # streams each touched page once; LUT + q rows ride along
                kv_tick = (2 * cfg.n_kv_heads * s_touch * tok_bytes
                           + plan.lut_bytes
                           + plan.rows * cfg.dh * 4)
                bytes_tick = kv_tick * n_attn_layers * slots
                result.setdefault(axis, {})["plan"] = \
                    dataclasses.asdict(plan)
            else:
                # gather reads the FULL block-table width, materializes a
                # contiguous f32 copy (write+read), then rep-expands it
                # to Hq for the GQA einsum (write+read again)
                kv_tick = (2 * cfg.n_kv_heads * s_max * tok_bytes
                           + 2 * cfg.n_kv_heads * s_max * cfg.dh * 4 * 2
                           + (2 * cfg.n_heads * s_max * cfg.dh * 4 * 2
                              if rep > 1 else 0))
                bytes_tick = kv_tick * n_attn_layers * slots
            tag = "fused  " if fused else "unfused"
            print(f"  {axis:10s} {tag}: {m['tokens_per_s']:8.1f} tok/s  "
                  f"calls {m['model_calls']:3d}  accept "
                  f"{m['draft_acceptance_rate']:.2f}  "
                  f"~{bytes_tick / 2**20:6.2f} MiB/tick  "
                  f"ops/trace {decode_ops}")
            result.setdefault(axis, {})[
                "fused" if fused else "unfused"] = {
                    "tokens_per_s": m["tokens_per_s"],
                    "model_calls": m["model_calls"],
                    "draft_acceptance_rate": m["draft_acceptance_rate"],
                    "wall_s": dt,
                    "attention_ops_traced": decode_ops,
                    "modeled_kv_bytes_per_tick": bytes_tick,
                }
        agree = outs[True] == outs[False]
        speedup = (mets[True]["tokens_per_s"]
                   / max(mets[False]["tokens_per_s"], 1e-9))
        result[axis]["greedy_agreement"] = float(agree)
        result[axis]["fused_speedup"] = speedup
        fb = result[axis]["fused"]["modeled_kv_bytes_per_tick"]
        ub = result[axis]["unfused"]["modeled_kv_bytes_per_tick"]
        result[axis]["modeled_bytes_ratio_unfused_over_fused"] = \
            ub / max(fb, 1)
        print(f"  {axis:10s} fused speedup {speedup:.2f}x, modeled "
              f"bytes/tick ratio {ub / fb:.1f}x, agree {agree}")
        if jax.default_backend() == "cpu" and pinned:
            assert agree, f"{axis}: megakernel changed greedy outputs"
            assert speedup >= 1.0, \
                f"{axis}: fused decode slower than unfused ({speedup:.2f}x)"
        elif not agree:
            print(f"  WARNING: {axis} fused vs unfused outputs differ "
                  "(near-tie flips across reduction orders — not "
                  "asserted off the pinned CPU workload)")
        csv_rows.append((f"roofline/fused_decode_{axis}_tok_per_s", 0.0,
                         mets[True]["tokens_per_s"]))
        csv_rows.append((f"roofline/fused_decode_{axis}_speedup", 0.0,
                         speedup))
    with open(out_path, "w") as fh:
        json.dump(result, fh, indent=2, sort_keys=True)
    print(f"  wrote {out_path}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--dense-baseline", action="store_true",
                    help="also run serve shapes with UNquantized weights "
                    "(pre-paper baseline)")
    ap.add_argument("--fused-decode-bench", action="store_true",
                    help="time the ragged decode megakernel against the "
                    "per-call kernel + page-gather path and write "
                    "BENCH_roofline.json (skips the roofline cells)")
    args = ap.parse_args()

    if args.fused_decode_bench:
        fused_decode_bench([])
        return 0
    # the roofline cells compile against the production mesh on host
    # devices; the fused-decode bench above times the real single-host
    # engine, where fake devices would skew every measurement
    set_host_device_count(512)

    archs = assigned_archs() if (args.all or not args.arch) else [args.arch]
    results = []
    for a in archs:
        cfg = get_config(a)
        for s, why in cfg.shapes():
            if args.shape and s.name != args.shape:
                continue
            if why:
                results.append({"arch": a, "shape": s.name, "status": why})
                print(f"[{a} x {s.name}] {why}")
                continue
            try:
                results.append(run_cell(a, s.name))
                if args.dense_baseline and s.kind != "train":
                    results.append(run_cell(a, s.name, quantized=False))
            except Exception as e:
                import traceback
                traceback.print_exc()
                results.append({"arch": a, "shape": s.name,
                                "status": f"FAILED: {e}"})
    n_bad = sum(1 for r in results if r and r["status"].startswith("FAIL"))
    print(f"\n{len(results)} cells, {n_bad} failures")
    return 1 if n_bad else 0


if __name__ == "__main__":
    raise SystemExit(main())
