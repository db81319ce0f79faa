"""Serving benchmark over KV-memory axes: same weights, same mixed-length
request batch, three cache configurations —

  dense-f32   per-slot (B, Hkv, max_seq, dh) f32 cache (the baseline)
  paged-bf16  block-table page pool, bf16 values
  paged-spx   block-table page pool, SPx-quantized codes + per-token scale
              (non-uniform 8-bit levels, fused-dequant decode kernel)

— reporting throughput, latency percentiles, page occupancy and peak KV
bytes, and checking greedy-output agreement against dense-f32 (paging is a
memory-layout change and 8-bit SPx KV must preserve greedy outputs on this
workload; both are asserted on the ref backend).

A second scenario drives a batch of requests sharing a page-aligned
system prompt through the paged engine with the prefix cache off vs on,
asserting identical greedy outputs, prefill-tokens-skipped > 0, and a
strictly lower peak page count with sharing — the acceptance criteria for
shared-prefix KV page reuse (docs/SERVING.md).

A third scenario drives a repetition-heavy workload (tiled-motif prompts,
the pattern prompt-lookup drafting feeds on) with speculative decoding
off vs on, asserting bit-identical greedy outputs, strictly fewer model
calls, and draft acceptance > 0 — both plain paged and paged+SPx-KV
(docs/SERVING.md, speculative decoding).

A fourth scenario replays a bursty oversubscribed arrival process —
low-priority background requests that fill the page pool, then a
high-priority burst mid-run — through the synchronous FIFO scheduler and
the continuous-batching scheduler on the SAME pool geometry, plain and
SPx-quantized KV. Asserted: the cb engine preempts (preemptions > 0,
offload_bytes == onload_bytes > 0, prefix_evictions > 0 under a
1-page prefix-cache budget) while every request's greedy output stays
bit-identical to the FIFO baseline (CPU; reported elsewhere). The
`preemptions` / `offload_bytes` / `prefix_evictions` totals are copied
to the top level of BENCH_serving.json for the CI checks job.

A streaming scenario serves the oversubscribed workload twice on
identical engines — whole-request `run()` vs per-rid token streams
polled by an external tick loop — asserting streamed tokens bit-identical
to `run()` and consumer-side streamed TTFT p50 strictly below the
whole-request latency p50, then cancels half a resubmitted wave mid-run
(pool `validate()` clean, survivors unchanged). The
`streaming.streamed_ttft_p50_ms` / `streaming.ttft_speedup` /
`streaming.requests_cancelled` keys are what the CI checks job asserts.

A fifth scenario is the unified-state-cache architecture matrix: an SSM
(xlstm-350m), a hybrid (jamba-1.5-large-398b), an encoder-decoder
(whisper-small) and an M-RoPE VLM decoder (qwen2-vl-2b), each reduced,
served dense+fifo vs paged+cb. Asserted: greedy outputs bit-identical
per request (CPU), paged peak_state_bytes strictly below dense for the
SSM/hybrid/enc-dec rows, and whisper's shared input frames hitting the
refcounted cross-KV region (cross_hits > 0). Per-arch results land in
BENCH_serving.json["arch_matrix"] for the CI checks job.

Standalone:  PYTHONPATH=src python -m benchmarks.serving_bench
From run.py: writes BENCH_serving.json at the repo root.
"""
from __future__ import annotations

import json
import os

import numpy as np

ARTIFACT = os.path.normpath(os.path.join(os.path.dirname(__file__), "..",
                                         "BENCH_serving.json"))

#: the non-uniform scheme the SPx axis runs (x=3 terms, 131 levels, 8-bit
#: codes — the paper's extension; see docs/QUANTIZATION.md)
SPX_SCHEME = "spx_8_x3"


def run(csv_rows, *, requests: int = 10, slots: int = 4, max_seq: int = 64,
        new_tokens: int = 8, seed: int = 3, out_path: str = ARTIFACT) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, reduced
    from repro.models import lm as lm_mod
    from repro.runtime import Runtime
    from repro.serving.engine import Request, ServeConfig, ServeEngine

    import dataclasses
    # Geometry notes. reduced() shrinks head_dim to 32, where the 4-byte
    # per-token scale would distort the SPx-vs-bf16 byte ratio (2*dh vs
    # dh+4) far below what serving-scale heads see (gemma-2b's real dh is
    # 256); benchmark at dh=128 — still CPU-cheap, ratio representative
    # (1.94x vs 1.97x). vocab=32 keeps the random-init model's top-2 logit
    # gaps wide relative to the ~2% SPx KV error, so the greedy-agreement
    # assertion checks quantization fidelity instead of coin-flip
    # near-ties (a 512-way random softmax is mostly ties at the top).
    cfg = dataclasses.replace(reduced(get_config("gemma-2b"), vocab=32),
                              head_dim=128)
    rt = Runtime(impl="auto", q_chunk=64)
    params = lm_mod.lm_init(jax.random.PRNGKey(seed), cfg)

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(rng.integers(4, max_seq // 2)))
               .astype(np.int32) for _ in range(requests)]

    # equal page geometry for the two paged axes so the peak-KV comparison
    # is purely bytes-per-token, not fragmentation of differing page sizes
    axes = {
        "dense-f32": dict(kv_layout="dense", rt=rt),
        "paged-bf16": dict(kv_layout="paged", rt=rt,
                           kv_cache_dtype=jnp.bfloat16, page_size=16),
        "paged-spx": dict(kv_layout="paged", page_size=16,
                          rt=rt.replace(kv_quant=True,
                                        kv_scheme=SPX_SCHEME)),
    }

    outputs = {}
    result = {"config": {"arch": cfg.name, "requests": requests,
                         "batch_slots": slots, "max_seq": max_seq,
                         "new_tokens": new_tokens,
                         "spx_scheme": SPX_SCHEME}}
    print("\n== serving: dense-f32 vs paged-bf16 vs paged-SPx KV ==")
    for axis, kw in axes.items():
        ert = kw.pop("rt")
        eng = ServeEngine(params, cfg,
                          ServeConfig(batch_slots=slots, max_seq=max_seq,
                                      quantize="sp2_4", **kw), rt=ert)
        # warmup pass: pay every jit compile (the paged engine compiles
        # O(log prefill_chunk) chunk-width variants vs dense's two steps —
        # timing a cold run would misattribute compile time to the layout)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
        eng.run()
        eng.reset_metrics()
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
        done = eng.run()
        outputs[axis] = {r.rid: r.output for r in done}
        m = eng.metrics()
        result[axis] = m
        print(f"  {axis:10s}: {m['tokens_per_s']:8.1f} tok/s  "
              f"p50 {m['latency_p50_ms']:7.0f}ms  "
              f"p95 {m['latency_p95_ms']:7.0f}ms  "
              f"peak KV {m['peak_kv_bytes'] / 2**10:7.2f} KiB  "
              f"occ {m['occupancy_mean']:.2f}/{m['occupancy_peak']:.2f}")
        csv_rows.append((f"serving/{axis}_tok_per_s", 0.0,
                         m["tokens_per_s"]))
        csv_rows.append((f"serving/{axis}_peak_kv_kib", 0.0,
                         m["peak_kv_bytes"] / 2**10))

    # greedy agreement vs the dense f32 baseline. On the ref backend, with
    # the DEFAULT pinned workload, the paged-bf16 rounding and the SPx
    # quantization error both preserve every greedy token — asserted, so a
    # regression in the fused-dequant path fails the harness. These are
    # genuinely lossy comparisons (unlike the old paged-f32-vs-dense-f32
    # layout check, which was exact by construction), so a CUSTOM workload
    # only reports: a near-tie top-1 flip there is quantization noise, not
    # a bug. Same on TPU, where the two layouts use different kernels and
    # reduction orders.
    pinned_workload = (requests, slots, max_seq, new_tokens, seed) \
        == (10, 4, 64, 8, 3)
    for axis in ("paged-bf16", "paged-spx"):
        agree = float(np.mean([outputs["dense-f32"][i] == outputs[axis][i]
                               for i in range(requests)]))
        if jax.default_backend() == "cpu" and pinned_workload:
            assert agree == 1.0, \
                f"dense-f32 vs {axis} greedy divergence: {agree}"
        elif agree < 1.0:
            print(f"  WARNING: dense-f32 vs {axis} agreement {agree:.3f} "
                  "< 1.0 (near-tie flips under quantization/reduction "
                  "order — not asserted off the pinned default workload)")
        result[f"greedy_agreement_{axis}"] = agree
        csv_rows.append((f"serving/greedy_agreement_{axis}", 0.0, agree))

    # the memory claim: SPx pages (1-byte codes + f32 scale) undercut the
    # bf16 pages by ~2x at matched geometry — dh/(dh+4)*2 exactly
    ratio_spx = (result["paged-bf16"]["peak_kv_bytes"]
                 / max(result["paged-spx"]["peak_kv_bytes"], 1))
    ratio_dense = (result["dense-f32"]["peak_kv_bytes"]
                   / max(result["paged-spx"]["peak_kv_bytes"], 1))
    result["kv_bytes_ratio_bf16_over_spx"] = ratio_spx
    result["kv_bytes_ratio_dense_over_spx"] = ratio_dense
    print(f"  peak-KV ratios: paged-bf16/paged-spx {ratio_spx:.2f}x, "
          f"dense-f32/paged-spx {ratio_dense:.2f}x")
    csv_rows.append(("serving/kv_ratio_bf16_over_spx", 0.0, ratio_spx))

    result["streaming"] = _streaming_scenario(csv_rows, params, cfg, rt)
    result["prefix_cache"] = _prefix_cache_scenario(csv_rows, params, cfg,
                                                    rt)
    result["spec_decode"] = _spec_decode_scenario(csv_rows, params, cfg,
                                                  rt)
    bursty = _bursty_scenario(csv_rows, params, cfg, rt)
    result["bursty"] = bursty
    # the three scheduler headline counters CI asserts on (ISSUE 7):
    # summed across the plain and SPx cb axes of the bursty scenario
    for k in ("preemptions", "offload_bytes", "prefix_evictions"):
        result[k] = bursty[k]
    # unified-state-cache acceptance: every architecture family serves
    # paged (CI asserts the four per-arch keys exist in the artifact)
    result["arch_matrix"] = _arch_matrix_scenario(csv_rows, rt)
    # tensor-parallel acceptance: 1-vs-2-shard tok/s + per-shard peak KV
    # bytes (needs two devices)
    sharded = _sharded_scenario(csv_rows)
    if sharded is not None:
        result["sharded"] = sharded

    with open(out_path, "w") as fh:
        json.dump(result, fh, indent=2, sort_keys=True)
    print(f"  wrote {out_path}")
    return result


def _streaming_scenario(csv_rows, params, cfg, rt, *, requests: int = 8,
                        slots: int = 2, max_seq: int = 64,
                        new_tokens: int = 8, seed: int = 3) -> dict:
    """Incremental-delivery scenario: the same oversubscribed workload
    (8 requests through 2 slots) served twice on identical engines —
    once collected whole from ``run()``, once consumed token-by-token
    through per-rid streams while an external loop ticks the engine.
    The streamed pass stamps each request's first *delivered* token
    with a consumer-side monotonic clock, the latency a user actually
    sees; under queueing it lands far below the whole-request latency
    that was the only observable before streaming.

    Asserted (delivery is a read-path change — deterministic on any
    backend): streamed token sequences bit-identical to the ``run()``
    outputs per request; streamed TTFT p50 strictly below the
    whole-request latency p50 of the same pass. A cancellation wave
    rides along: half the requests are cancelled mid-run, the pool's
    ``validate()`` must stay clean and the survivors' outputs stay
    bit-identical."""
    import time

    from repro.serving.engine import Request, ServeConfig, ServeEngine

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(rng.integers(4, max_seq // 2)))
               .astype(np.int32) for _ in range(requests)]
    kw = dict(batch_slots=slots, max_seq=max_seq, quantize="sp2_4",
              kv_layout="paged")

    print("\n== serving: whole-request run() vs per-request streams ==")
    # whole-request baseline (warmup pays the compiles, as everywhere)
    base = ServeEngine(params, cfg, ServeConfig(**kw), rt=rt)
    for measured in (False, True):
        for i, p in enumerate(prompts):
            base.submit(Request(rid=i, prompt=p,
                                max_new_tokens=new_tokens))
        done = base.run()
        if not measured:
            base.reset_metrics()
    base_out = {r.rid: r.output for r in done}
    base_m = base.metrics()

    # streamed pass: identical engine, but a delivery loop polls every
    # stream after each tick and timestamps the first delivered token
    eng = ServeEngine(params, cfg, ServeConfig(**kw), rt=rt)
    for i, p in enumerate(prompts):                  # warmup
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
    eng.run()
    eng.reset_metrics()
    reqs = [Request(rid=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    streams, collected, t_first = {}, {}, {}
    for req in reqs:
        eng.submit(req)
        streams[req.rid] = eng.stream(req.rid)
        collected[req.rid] = []
    while eng.has_work():
        eng.step()
        now = time.monotonic()
        for rid, s in streams.items():
            toks = s.poll()
            if toks and rid not in t_first:
                t_first[rid] = now
            collected[rid].extend(toks)
    assert collected == base_out, \
        "streamed tokens diverged from run() outputs"
    m = eng.metrics()
    sttft = sorted(1e3 * (t_first[r.rid] - r.t_enqueue) for r in reqs)
    ttft_p50 = sttft[len(sttft) // 2]
    assert ttft_p50 < m["latency_p50_ms"], \
        (ttft_p50, m["latency_p50_ms"])
    speedup = m["latency_p50_ms"] / max(ttft_p50, 1e-9)
    print(f"  streamed TTFT p50 {ttft_p50:7.1f}ms vs whole-request "
          f"latency p50 {m['latency_p50_ms']:7.1f}ms "
          f"({speedup:.1f}x earlier first token)")

    # cancellation wave: odd rids die after two ticks; the pool must
    # account clean and the survivors must not notice
    eng.reset_metrics()
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
    eng.step()
    eng.step()
    cancelled = [i for i in range(requests) if i % 2]
    for rid in cancelled:
        eng.cancel(rid)
    eng.pool.validate()
    survivors = {r.rid: r.output for r in eng.run()}
    eng.pool.validate()
    cm = eng.metrics()
    assert cm["requests_cancelled"] == len(cancelled), cm
    assert sorted(survivors) == [i for i in range(requests) if not i % 2]
    assert all(survivors[i] == base_out[i] for i in survivors), \
        "cancellation disturbed surviving requests"
    print(f"  cancelled {cm['requests_cancelled']}/{requests} mid-run, "
          f"pool validate clean, survivors bit-identical")

    csv_rows.append(("serving/streamed_ttft_p50_ms", 0.0, ttft_p50))
    csv_rows.append(("serving/streamed_ttft_speedup", 0.0, speedup))
    return {"config": {"requests": requests, "batch_slots": slots,
                       "max_seq": max_seq, "new_tokens": new_tokens},
            "streamed_ttft_p50_ms": ttft_p50,
            "streamed_ttft_p95_ms": sttft[int(0.95 * (len(sttft) - 1))],
            "whole_request_latency_p50_ms": m["latency_p50_ms"],
            "ttft_speedup": speedup,
            "requests_cancelled": cm["requests_cancelled"],
            "run_metrics": base_m, "stream_metrics": m}


def _prefix_cache_scenario(csv_rows, params, cfg, rt, *, requests: int = 8,
                           slots: int = 2, max_seq: int = 64,
                           new_tokens: int = 4, seed: int = 3) -> dict:
    """Shared-system-prompt scenario: every request carries the same
    page-aligned 24-token system prompt (one of them is the *bare* system
    prompt, which exercises the copy-on-write path). Request 0 primes the
    pool alone, then the rest arrive as a wave through ``slots`` batch
    slots — with the prefix cache on, every later request maps the cached
    system-prompt pages instead of re-prefilling them.

    Asserted (acceptance criteria, deterministic on any backend — these
    are scheduling/accounting claims, not numerics): greedy outputs
    identical with sharing on vs off, prefill-tokens-skipped > 0, and
    peak KV pages strictly lower with sharing."""
    from repro.serving.engine import Request, ServeConfig, ServeEngine

    page_size = 8
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, cfg.vocab_size, 3 * page_size) \
        .astype(np.int32)
    prompts = [sys_prompt.copy()]                    # primer
    prompts += [np.concatenate(
        [sys_prompt,
         rng.integers(0, cfg.vocab_size,
                      int(rng.integers(1, 6))).astype(np.int32)])
        for _ in range(requests - 2)]
    prompts.append(sys_prompt.copy())                # bare again -> COW

    outputs, mets = {}, {}
    print("\n== serving: shared system prompt, prefix cache off vs on ==")
    for on in (False, True):
        eng = ServeEngine(params, cfg,
                          ServeConfig(batch_slots=slots, max_seq=max_seq,
                                      quantize="sp2_4", kv_layout="paged",
                                      page_size=page_size, prefix_cache=on),
                          rt=rt)
        eng.submit(Request(rid=0, prompt=prompts[0],
                           max_new_tokens=new_tokens))
        eng.run()                                    # prime the pool
        for i, p in enumerate(prompts[1:], start=1):
            eng.submit(Request(rid=i, prompt=p,
                               max_new_tokens=new_tokens))
        eng.run()
        outputs[on] = {r.rid: r.output for r in eng.finished}
        m = eng.metrics()
        mets[on] = m
        tag = "on " if on else "off"
        print(f"  prefix-cache {tag}: peak {m['peak_kv_pages']:3d} pages  "
              f"hits {m['prefix_hits']}  skipped "
              f"{m['prefill_tokens_skipped']} tok  cow {m['cow_copies']}  "
              f"{m['tokens_per_s']:8.1f} tok/s")

    assert outputs[True] == outputs[False], \
        "prefix cache changed greedy outputs"
    assert mets[False]["prefill_tokens_skipped"] == 0
    assert mets[True]["prefill_tokens_skipped"] > 0, \
        "prefix cache never skipped prefill work"
    assert mets[True]["peak_kv_pages"] < mets[False]["peak_kv_pages"], \
        (mets[True]["peak_kv_pages"], mets[False]["peak_kv_pages"])
    assert mets[True]["cow_copies"] >= 1, "COW path never exercised"

    hit_rate = mets[True]["prefix_hits"] / requests
    csv_rows.append(("serving/prefix_hit_rate", 0.0, hit_rate))
    csv_rows.append(("serving/prefix_tokens_skipped", 0.0,
                     mets[True]["prefill_tokens_skipped"]))
    csv_rows.append(("serving/prefix_peak_pages_ratio", 0.0,
                     mets[True]["peak_kv_pages"]
                     / mets[False]["peak_kv_pages"]))
    return {"config": {"requests": requests, "batch_slots": slots,
                       "page_size": page_size, "system_prompt_tokens":
                       int(len(sys_prompt)), "new_tokens": new_tokens},
            "hit_rate": hit_rate,
            "off": mets[False], "on": mets[True]}


def _spec_decode_scenario(csv_rows, params, cfg, rt, *, requests: int = 6,
                          slots: int = 2, max_seq: int = 64,
                          new_tokens: int = 12, spec_k: int = 4,
                          seed: int = 3) -> dict:
    """Repetition-heavy workload (each prompt tiles a short motif — the
    structure prompt-lookup drafting exploits, and the structure greedy
    decode on small models degenerates into anyway) through the paged
    engine, speculation off vs on, plain and SPx-quantized KV pages.

    Asserted on CPU, where both decode paths are deterministic jnp
    (acceptance criteria for prompt-lookup speculative decoding): greedy
    outputs **bit-identical** with speculation on vs off per KV axis,
    `model_calls` **strictly lower** with speculation, and
    `draft_acceptance_rate` > 0. Off CPU everything is reported, nothing
    asserted: equality compares the C==1 decode kernel against the K+1
    chunk-path verify window (different reduction orders), and the call/
    acceptance claims ride the same argmaxes, so a near-tie flip could
    break the repetition the drafter feeds on."""
    from repro.serving.engine import Request, ServeConfig, ServeEngine

    rng = np.random.default_rng(seed)
    prompts = [np.tile(rng.integers(0, cfg.vocab_size, 4).astype(np.int32),
                       4) for _ in range(requests)]
    axes = {"paged": rt,
            "paged-spx": rt.replace(kv_quant=True, kv_scheme=SPX_SCHEME)}
    report: dict = {"config": {"requests": requests, "batch_slots": slots,
                               "new_tokens": new_tokens, "spec_k": spec_k}}
    print("\n== serving: speculative decoding off vs on (prompt lookup) ==")
    for axis, ert in axes.items():
        outs, mets = {}, {}
        for spec in (False, True):
            eng = ServeEngine(params, cfg,
                              ServeConfig(batch_slots=slots, max_seq=max_seq,
                                          quantize="sp2_4", kv_layout="paged",
                                          spec_decode=spec,
                                          spec_k=spec_k if spec else None),
                              rt=ert)
            for i, p in enumerate(prompts):        # warmup: pay compiles
                eng.submit(Request(rid=i, prompt=p,
                                   max_new_tokens=new_tokens))
            eng.run()
            eng.reset_metrics()
            for i, p in enumerate(prompts):
                eng.submit(Request(rid=i, prompt=p,
                                   max_new_tokens=new_tokens))
            outs[spec] = {r.rid: r.output for r in eng.run()}
            mets[spec] = eng.metrics()
        on, off = mets[True], mets[False]
        print(f"  {axis:10s}: calls {off['model_calls']:3d} -> "
              f"{on['model_calls']:3d}  accepted/step "
              f"{on['accepted_per_step']:.2f}  acceptance "
              f"{on['draft_acceptance_rate']:.2f}  "
              f"{on['tokens_per_s']:8.1f} tok/s (was "
              f"{off['tokens_per_s']:.1f})")
        import jax
        agree = outs[True] == outs[False]
        if jax.default_backend() == "cpu":
            # acceptance (and so the call saving) rides the target
            # model's argmaxes, which off-CPU can near-tie-flip between
            # the C==1 decode kernel and the K+1 verify window — so all
            # three claims hard-assert only where they are deterministic
            assert agree, f"{axis}: speculation changed greedy outputs"
            assert on["model_calls"] < off["model_calls"], \
                (axis, on["model_calls"], off["model_calls"])
            assert on["draft_acceptance_rate"] > 0, axis
        elif not agree:
            print(f"  WARNING: {axis} spec-on vs spec-off outputs differ "
                  "(near-tie flips across the decode-kernel vs "
                  "verify-window reduction orders — not asserted off "
                  "CPU)")
        report[f"greedy_agreement_{axis}"] = float(agree)
        csv_rows.append((f"serving/spec_{axis}_acceptance", 0.0,
                         on["draft_acceptance_rate"]))
        csv_rows.append((f"serving/spec_{axis}_model_calls_ratio", 0.0,
                         on["model_calls"] / off["model_calls"]))
        report[axis] = {"off": off, "on": on}
    return report


def _bursty_scenario(csv_rows, params, cfg, rt, *, seed: int = 3) -> dict:
    """Bursty oversubscribed arrival process, FIFO vs continuous batching.

    Two priority-0 background requests (4 pages each) fill an 8-page pool
    at tick 0; three priority-5 burst requests (3 pages each) arrive at
    ticks 3-4 with zero free pages, so the cb scheduler must preempt a
    background — offloading its written KV pages to the host tier — and
    resume it after the burst drains. Every prompt shares a 2-page system
    prefix and the cb engine runs the prefix cache under a 1-page budget,
    so finishing requests overflow the cached-free index and force LRU
    evictions. The FIFO engine replays the identical arrival schedule on
    the identical pool.

    Asserted on every backend (scheduling/accounting claims — they depend
    only on request lengths, never on numerics): cb preempts > 0 times,
    offload_bytes == onload_bytes > 0, ends with an empty host tier, and
    evicts > 0 prefix pages; fifo does none of that. Asserted on CPU,
    where greedy argmaxes are deterministic across batch compositions:
    per-request outputs bit-identical fifo vs cb, plain AND SPx-quantized
    pools (the acceptance criterion for the continuous-batching PR)."""
    import jax
    from repro.serving.engine import Request, ServeConfig, ServeEngine

    page_size, slots, pool_pages, max_seq = 8, 2, 8, 48
    rng = np.random.default_rng(seed)
    sys_prompt = rng.integers(0, cfg.vocab_size, 2 * page_size) \
        .astype(np.int32)
    # (arrival_tick, rid, tail_tokens, new_tokens, priority)
    schedule = [(0, 0, 10, 6, 0), (0, 1, 10, 6, 0),     # background: 4 pg
                (3, 2, 4, 4, 5), (3, 3, 4, 4, 5),       # burst: 3 pg
                (4, 4, 4, 4, 5)]
    tails = {rid: rng.integers(0, cfg.vocab_size, n).astype(np.int32)
             for _, rid, n, _, _ in schedule}

    def drive(eng):
        """Replay the arrival schedule through public step()/run()."""
        pending = sorted(schedule)
        i = 0
        for t in range(len(pending) * 8):
            while i < len(pending) and pending[i][0] <= t:
                _, rid, _, new, pri = pending[i]
                eng.submit(Request(
                    rid=rid,
                    prompt=np.concatenate([sys_prompt, tails[rid]]),
                    max_new_tokens=new, priority=pri))
                i += 1
            if i == len(pending):
                break
            eng.step()
        eng.run(max_steps=400)
        assert eng.drained
        return {r.rid: r.output for r in eng.finished}

    axes = {"paged": rt,
            "paged-spx": rt.replace(kv_quant=True, kv_scheme=SPX_SCHEME)}
    report: dict = {"config": {"schedule": schedule, "page_size": page_size,
                               "batch_slots": slots,
                               "pool_pages": pool_pages,
                               "system_prompt_tokens": int(len(sys_prompt)),
                               "prefix_cache_pages": 1},
                    "preemptions": 0, "offload_bytes": 0,
                    "prefix_evictions": 0}
    print("\n== serving: bursty oversubscription, fifo vs cb scheduler ==")
    for axis, ert in axes.items():
        outs, mets = {}, {}
        for sched in ("fifo", "cb"):
            eng = ServeEngine(params, cfg,
                              ServeConfig(batch_slots=slots, max_seq=max_seq,
                                          quantize="sp2_4",
                                          kv_layout="paged",
                                          page_size=page_size,
                                          pool_pages=pool_pages,
                                          scheduler=sched,
                                          prefix_cache=(sched == "cb"),
                                          prefix_cache_pages=(
                                              1 if sched == "cb" else None)),
                              rt=ert)
            outs[sched] = drive(eng)
            mets[sched] = eng.metrics()
        cb, fifo = mets["cb"], mets["fifo"]
        print(f"  {axis:10s}: preemptions {cb['preemptions']}  "
              f"offload {cb['offload_bytes']} B  "
              f"prefix evictions {cb['prefix_evictions']}  "
              f"(fifo: denials {fifo['admission_denials']})")
        # scheduling claims — deterministic on any backend
        assert cb["preemptions"] > 0, f"{axis}: burst never preempted"
        assert cb["resumes"] > 0, axis
        assert cb["offload_bytes"] == cb["onload_bytes"] > 0, \
            (axis, cb["offload_bytes"], cb["onload_bytes"])
        assert cb["host_pages_in_use"] == 0, \
            f"{axis}: host tier not drained"
        assert cb["prefix_evictions"] > 0, \
            f"{axis}: 1-page cache budget never evicted"
        assert fifo["preemptions"] == fifo["offload_bytes"] == 0
        agree = outs["cb"] == outs["fifo"]
        if jax.default_backend() == "cpu":
            assert agree, f"{axis}: cb scheduler changed greedy outputs"
        elif not agree:
            print(f"  WARNING: {axis} cb vs fifo outputs differ (near-tie "
                  "flips across batch compositions — not asserted off CPU)")
        report[f"greedy_agreement_{axis}"] = float(agree)
        report[axis] = {"fifo": fifo, "cb": cb}
        report["preemptions"] += cb["preemptions"]
        report["offload_bytes"] += cb["offload_bytes"]
        report["prefix_evictions"] += cb["prefix_evictions"]
        csv_rows.append((f"serving/bursty_{axis}_preemptions", 0.0,
                         cb["preemptions"]))
        csv_rows.append((f"serving/bursty_{axis}_offload_kib", 0.0,
                         cb["offload_bytes"] / 2**10))
    return report


def _arch_matrix_scenario(csv_rows, rt, *, slots: int = 4,
                          max_seq: int = 64, new_tokens: int = 8,
                          seed: int = 3) -> dict:
    """Architecture matrix for the unified state cache: one SSM
    (xlstm-350m), one hybrid (jamba-1.5-large-398b), one enc-dec
    (whisper-small) and one M-RoPE VLM decoder (qwen2-vl-2b) — each at
    reduced scale — served dense+fifo vs paged+cb on the same weights
    and requests (3 requests through 4 slots; the whisper requests
    include two sharing identical input frames, so the encoder output
    is computed once and its cross entry refcount-shared).

    Asserted on CPU, where greedy argmaxes are deterministic across
    batch compositions: per-request greedy outputs bit-identical paged
    vs dense for every architecture. Asserted on any backend
    (accounting claims): paged peak_state_bytes strictly below the
    dense baseline for the SSM, hybrid and enc-dec rows — dense bills
    every batch slot's worst case (full-length KV + slab + cross) while
    the state cache bills only live sequences — and whisper records
    cross_hits > 0 for the shared frames. The per-arch keys in
    BENCH_serving.json["arch_matrix"] are what the CI checks job
    asserts on."""
    import jax
    from repro.configs import get_config, reduced
    from repro.models import encdec as encdec_mod
    from repro.models import lm as lm_mod
    from repro.serving.engine import Request, ServeConfig, ServeEngine

    def build(arch):
        if arch == "whisper-small":
            cfg = reduced(get_config("whisper-small"))
            params = encdec_mod.encdec_init(jax.random.PRNGKey(2), cfg)
            fr = np.asarray(jax.random.normal(
                jax.random.PRNGKey(seed),
                (2, cfg.enc_seq_len, cfg.d_model)))
            return cfg, params, [fr[0], fr[0], fr[1]]  # 0 and 1 share
        n_layers = {"xlstm-350m": 4, "jamba-1.5-large-398b": 8,
                    "qwen2-vl-2b": 2}[arch]
        cfg = reduced(get_config(arch), n_layers=n_layers)
        return cfg, lm_mod.lm_init(jax.random.PRNGKey(1), cfg), None

    report: dict = {"config": {"batch_slots": slots, "max_seq": max_seq,
                               "new_tokens": new_tokens, "requests": 3}}
    print("\n== serving: architecture matrix, dense+fifo vs paged+cb ==")
    for arch in ("xlstm-350m", "jamba-1.5-large-398b", "whisper-small",
                 "qwen2-vl-2b"):
        cfg, params, frames = build(arch)
        rng = np.random.default_rng(seed)
        prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
                   for n in (7, 19, 12)]
        outs, mets = {}, {}
        for layout, sched in (("dense", "fifo"), ("paged", "cb")):
            eng = ServeEngine(params, cfg,
                              ServeConfig(batch_slots=slots, max_seq=max_seq,
                                          quantize=None, kv_layout=layout,
                                          scheduler=sched),
                              rt=rt)
            for i, p in enumerate(prompts):
                eng.submit(Request(
                    rid=i, prompt=p, max_new_tokens=new_tokens,
                    frames=None if frames is None else frames[i]))
            eng.run(max_steps=2000)
            assert eng.drained
            outs[layout] = {r.rid: r.output for r in eng.finished}
            mets[layout] = eng.metrics()
        mp, md = mets["paged"], mets["dense"]
        agree = outs["paged"] == outs["dense"]
        if jax.default_backend() == "cpu":
            assert agree, f"{arch}: paged+cb changed greedy outputs"
        elif not agree:
            print(f"  WARNING: {arch} paged vs dense outputs differ "
                  "(near-tie flips across layouts — not asserted off "
                  "CPU)")
        if arch != "qwen2-vl-2b":
            # the memory claim for SSM/hybrid/enc-dec state: 3 live
            # requests vs 4 always-billed dense slots
            assert mp["peak_state_bytes"] < md["peak_state_bytes"], \
                (arch, mp["peak_state_bytes"], md["peak_state_bytes"])
        if frames is not None:
            assert mp["cross_hits"] > 0, "shared frames never reused"
            assert mp["peak_cross"] == 2, mp["peak_cross"]
        ratio = md["peak_state_bytes"] / max(mp["peak_state_bytes"], 1)
        print(f"  {arch:22s}: agree {int(agree)}  peak state "
              f"{mp['peak_state_bytes']:8d} B paged vs "
              f"{md['peak_state_bytes']:8d} B dense ({ratio:.2f}x)")
        report[arch] = {"greedy_agreement": float(agree),
                        "state_bytes_ratio_dense_over_paged": ratio,
                        "dense": md, "paged": mp}
        csv_rows.append((f"serving/arch_{arch}_state_ratio", 0.0, ratio))
        csv_rows.append((f"serving/arch_{arch}_greedy_agreement", 0.0,
                         float(agree)))
    return report


def sharded_serving(*, requests: int = 8, slots: int = 4, max_seq: int = 64,
                  new_tokens: int = 8, seed: int = 3) -> dict:
    """Serve the pinned workload at shards=1 and shards=2 and report
    throughput, per-shard peak KV bytes and greedy agreement."""
    import dataclasses

    import jax

    from repro.configs import get_config, reduced
    from repro.models import lm as lm_mod
    from repro.runtime import Runtime
    from repro.serving.engine import Request, ServeConfig, ServeEngine

    # the pinned geometry from run(), plus n_kv_heads=2 so the 2-wide
    # model axis gets one KV head per shard (reduced gemma-2b's single
    # KV head can't split — it would silently replicate)
    cfg = dataclasses.replace(reduced(get_config("gemma-2b"), vocab=32),
                              head_dim=128, n_kv_heads=2)
    rt = Runtime(impl="auto", q_chunk=64)
    params = lm_mod.lm_init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size,
                            int(rng.integers(4, max_seq // 2)))
               .astype(np.int32) for _ in range(requests)]

    out: dict = {"config": {"arch": cfg.name, "requests": requests,
                            "batch_slots": slots, "max_seq": max_seq,
                            "new_tokens": new_tokens,
                            "n_kv_heads": cfg.n_kv_heads,
                            "host_devices": jax.device_count()}}
    outputs = {}
    for shards in (1, 2):
        eng = ServeEngine(params, cfg,
                          ServeConfig(batch_slots=slots, max_seq=max_seq,
                                      quantize="sp2_4", kv_layout="paged",
                                      page_size=16, shards=shards), rt=rt)
        for i, p in enumerate(prompts):            # warmup: pay compiles
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
        eng.run()
        eng.reset_metrics()
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))
        done = eng.run()
        outputs[shards] = {r.rid: r.output for r in done}
        m = eng.metrics()
        out[f"shards_{shards}"] = {
            "tokens_per_s": m["tokens_per_s"],
            "peak_kv_bytes": m["peak_kv_bytes"],
            "peak_kv_bytes_per_shard": m["peak_kv_bytes_per_shard"],
            "kv_sharded": m["kv_sharded"],
            "kv_heads_per_shard": m["kv_heads_per_shard"]}
    out["greedy_agreement"] = float(np.mean(
        [outputs[1][i] == outputs[2][i] for i in range(requests)]))
    return out


def _sharded_scenario(csv_rows) -> dict | None:
    """Tensor-parallel serving, in this process: run ``sharded_serving``
    and assert the sharded contract — greedy outputs bit-identical across
    shard counts, 2-shard KV head-sharded with per-shard peak bytes
    halved. Needs two devices: on CPU, force host devices before jax
    starts (XLA_FLAGS=--xla_force_host_platform_device_count=8, as the
    CI checks job does). With one device the scenario is skipped and
    returns None. Keys land in BENCH_serving.json["sharded"]."""
    import jax

    if jax.device_count() < 2:
        print("\n== serving: tensor-parallel skipped (one device) ==")
        return None
    print(f"\n== serving: tensor-parallel, 1 vs 2 shards "
          f"({jax.device_count()} devices) ==")
    payload = sharded_serving()

    s1, s2 = payload["shards_1"], payload["shards_2"]
    for n, s in (("1", s1), ("2", s2)):
        print(f"  shards={n}: {s['tokens_per_s']:8.1f} tok/s  "
              f"peak KV/shard {s['peak_kv_bytes_per_shard'] / 2**10:7.2f} "
              f"KiB")
        csv_rows.append((f"serving/sharded_{n}_tok_per_s", 0.0,
                         s["tokens_per_s"]))
        csv_rows.append((f"serving/sharded_{n}_peak_kv_kib_per_shard", 0.0,
                         s["peak_kv_bytes_per_shard"] / 2**10))
    assert payload["greedy_agreement"] == 1.0, payload
    assert s2["kv_sharded"] is True and s2["kv_heads_per_shard"] == 1, s2
    assert s2["peak_kv_bytes_per_shard"] < s1["peak_kv_bytes_per_shard"], \
        (s1, s2)
    print(f"  greedy agreement {payload['greedy_agreement']:.0f}, "
          f"per-shard peak KV {s1['peak_kv_bytes_per_shard']} B -> "
          f"{s2['peak_kv_bytes_per_shard']} B")
    csv_rows.append(("serving/sharded_greedy_agreement", 0.0,
                     payload["greedy_agreement"]))
    return payload


if __name__ == "__main__":
    run([])
