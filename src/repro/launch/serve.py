"""Serving driver: spin up the batched engine with SPx-quantized weights and
run a synthetic request workload, reporting latency/throughput/occupancy.

  PYTHONPATH=src python -m repro.launch.serve --arch gemma-2b --reduced \
      --requests 16 --scheme sp2_4 --kv-layout paged \
      --kv-quant --kv-scheme spx_8_x3

--arch accepts every bundled config, not just attention-only decoders:
SSM (xlstm-350m), hybrid (jamba-1.5-large-398b) and M-RoPE
(qwen2-vl-2b) configs serve through the unified state cache's slab
region, and enc-dec (whisper-small) runs with synthetic input frames —
two distinct inputs alternate across requests, so identical inputs
share one encoder pass through the cross-KV region (docs/SERVING.md,
"The unified state cache").

Weight quantization (--scheme) and KV-cache quantization (--kv-quant +
--kv-scheme, uniform8 baseline or non-uniform SPx) are independent axes;
both compose with either KV layout — see docs/QUANTIZATION.md.

--prefix-cache turns on shared-prefix KV page reuse: requests whose
prompts share a page-aligned prefix (a common system prompt) map the same
physical pages instead of re-prefilling them — docs/SERVING.md.

--spec-decode turns on prompt-lookup speculative decoding (paged layout):
an n-gram drafter proposes up to --spec-k tokens per decode tick and one
verify pass scores the whole window, so repetitive outputs cost fewer
model calls per token — docs/SERVING.md.

--scheduler picks the admission policy: 'cb' (continuous batching —
priority admission with preemption + KV page offload to a host tier,
the paged default) or 'fifo' (the synchronous head-blocks-queue
baseline). --host-pages bounds the offload tier, --prefix-cache-pages
bounds the cached-free prefix index (LRU eviction) — docs/SERVING.md.

--shards runs the engine tensor-parallel over a ``model`` mesh axis
(head-sharded KV pools, replicated block tables); --replicas stacks
data-parallel engine replicas behind a least-loaded router. On CPU,
force host devices first: XLA_FLAGS=--xla_force_host_platform_device_count=8
(repro.launch.hostdev) — docs/SERVING.md, "Sharded serving".

Env knobs that reach serving: REPRO_PAGE_SIZE (tokens per KV page),
REPRO_PREFILL_CHUNK (chunked-prefill length), REPRO_PREFIX_CACHE=1
(prefix cache default), REPRO_SPEC_K=N (speculative decoding default +
window), REPRO_SCHEDULER / REPRO_HOST_PAGES / REPRO_PREFIX_CACHE_PAGES
(scheduler + two-tier pool defaults), REPRO_SHARDS / REPRO_REPLICAS
(parallelism defaults), REPRO_BLOCKS_* / REPRO_AUTOTUNE (kernel tiles)
— all resolved in one place, ServeConfig.resolve() (docs/SERVING.md).
"""
from __future__ import annotations

import argparse
import asyncio
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced
from repro.configs.base import ArchConfig
from repro.core import spx
from repro.launch.compile_cache import enable_compile_cache
from repro.models import encdec as encdec_mod
from repro.models import lm as lm_mod
from repro.nn.layers import quantize_params
from repro.runtime import Runtime
from repro.serving import ReplicaRouter
from repro.serving.engine import Request, ServeConfig, ServeEngine


def init_params(cfg: ArchConfig, seed: int, scheme: str | None):
    """Random weights for ``cfg`` from ``seed``, SPx-quantized under
    ``scheme`` (None keeps them dense). Decoder-only configs (dense, MoE,
    SSM, hybrid, M-RoPE) init through the LM assembly, enc-dec through the
    encoder-decoder one. The f32 tree is unreferenced once quantized, so
    only the quantized weights stay on the device — at qwen2-vl-2b width
    that is 7.1 GB of f32 traded for 0.66 GB of codes plus the 1.9 GB
    embedding and head, which stay dense."""
    init = encdec_mod.encdec_init if cfg.enc_dec else lm_mod.lm_init
    params = init(jax.random.PRNGKey(seed), cfg)
    return quantize_params(params, scheme) if scheme else params


def build_engine(params, cfg: ArchConfig, sconf: ServeConfig, rt: Runtime):
    """The serving front end for a resolved ``sconf``: a ReplicaRouter
    when it asks for replicas, otherwise one ServeEngine."""
    if sconf.replicas > 1:
        return ReplicaRouter(params, cfg, sconf, rt=rt)
    return ServeEngine(params, cfg, sconf, rt=rt)


def _run_streaming(eng, reqs, arrival_s: float):
    """Asyncio front-end over the tick-driven engine: one driver task
    steps the engine whenever it has work, a submitter feeds requests in
    over time (arrival overlaps compute), and one consumer per request
    drains ``async for tok in eng.stream(rid)`` as tokens are emitted —
    all on one event loop, no threads. Returns the finished list plus a
    per-rid monotonic stamp of the first *delivered* token, the
    user-visible TTFT the batch path cannot measure."""
    delivered: dict[int, float] = {}

    async def consume(req):
        async for _tok in eng.stream(req.rid):
            delivered.setdefault(req.rid, time.monotonic())

    async def submit_all(consumers):
        for req in reqs:
            eng.submit(req)
            consumers.append(asyncio.ensure_future(consume(req)))
            await asyncio.sleep(arrival_s)

    async def amain():
        consumers: list = []
        sub = asyncio.ensure_future(submit_all(consumers))
        # tick while anything is arriving or in flight, yielding after
        # every tick so consumers drain the tokens it just emitted
        while not sub.done() or eng.has_work():
            if eng.has_work():
                eng.step()
                await asyncio.sleep(0)
            else:
                await asyncio.sleep(arrival_s / 4)
        await sub
        await asyncio.gather(*consumers)

    asyncio.run(amain())
    return list(eng.finished), delivered


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--scheme", default="sp2_4",
                    help="SPx scheme for weights; 'none' = dense bf16")
    ap.add_argument("--kv-layout", default="auto",
                    choices=("auto", "paged", "dense"),
                    help="paged = block-table KV pool + chunked prefill")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page (default: planner-chosen)")
    ap.add_argument("--pool-pages", type=int, default=None,
                    help="KV pool size in pages (default: dense-equivalent)")
    ap.add_argument("--prefill-chunk", type=int, default=None)
    ap.add_argument("--prefix-cache", action="store_true", default=None,
                    help="share page-aligned prompt-prefix KV pages across "
                         "requests (paged layout only; REPRO_PREFIX_CACHE=1 "
                         "sets the default)")
    ap.add_argument("--shared-prefix", type=int, default=0, metavar="N",
                    help="prepend the same N-token system prompt to every "
                         "synthetic request (exercises --prefix-cache)")
    ap.add_argument("--spec-decode", action="store_true", default=None,
                    help="prompt-lookup speculative decoding (paged layout "
                         "only; REPRO_SPEC_K=N sets the default)")
    ap.add_argument("--spec-k", type=int, default=None, metavar="K",
                    help="draft window for --spec-decode (default 4; "
                         "passing it alone implies --spec-decode)")
    ap.add_argument("--scheduler", default=None, choices=("fifo", "cb"),
                    help="admission policy: cb = continuous batching with "
                         "priority preemption + KV offload (paged default), "
                         "fifo = synchronous head-blocks-queue baseline "
                         "(REPRO_SCHEDULER sets the default)")
    ap.add_argument("--host-pages", type=int, default=None, metavar="N",
                    help="host offload tier capacity in pages (paged "
                         "layout; default unbounded, REPRO_HOST_PAGES "
                         "sets the default)")
    ap.add_argument("--prefix-cache-pages", type=int, default=None,
                    metavar="N",
                    help="cached-free prefix index budget in pages — LRU "
                         "eviction past it (default unbounded, "
                         "REPRO_PREFIX_CACHE_PAGES sets the default)")
    ap.add_argument("--shards", type=int, default=None, metavar="N",
                    help="tensor-parallel shards over the 'model' mesh axis "
                         "(paged layout; needs N devices — on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count; "
                         "REPRO_SHARDS sets the default)")
    ap.add_argument("--replicas", type=int, default=None, metavar="N",
                    help="data-parallel engine replicas behind a "
                         "least-loaded router, each with a per-replica "
                         "page budget (REPRO_REPLICAS sets the default)")
    ap.add_argument("--kv-quant", action="store_true",
                    help="quantize the KV cache to codes+scale pages")
    ap.add_argument("--kv-scheme", default="spx_8_x3",
                    choices=sorted(s for s in spx.SCHEMES
                                   if spx.code_width(
                                       spx.scheme_levels(s)) == 8),
                    help="level set for --kv-quant (8-bit-code schemes)")
    ap.add_argument("--kv-dtype", default="f32", choices=("f32", "bf16"),
                    help="unquantized KV cache element dtype")
    ap.add_argument("--stream", action="store_true",
                    help="asyncio front-end: request arrival overlaps "
                         "engine ticks and each request's tokens are "
                         "consumed as they are emitted (async for over "
                         "engine.stream(rid)) — TTFT becomes time to "
                         "first *delivered* token. docs/SERVING.md, "
                         "'Streaming delivery and cancellation'.")
    ap.add_argument("--arrival-ms", type=float, default=0.0, metavar="MS",
                    help="gap between request arrivals under --stream "
                         "(0 = back-to-back, still interleaved with ticks)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)

    # enc-dec configs serve synthetic input frames — two distinct inputs
    # alternating across requests, so the state cache's shared cross-KV
    # region sees hits (docs/SERVING.md)
    frame_sets = None
    if cfg.enc_dec:
        fr_rng = np.random.default_rng(args.seed + 1)
        frame_sets = fr_rng.standard_normal(
            (2, cfg.enc_seq_len, cfg.d_model)).astype(np.float32)
    scheme = None if args.scheme == "none" else args.scheme
    rt = Runtime(impl="auto", q_chunk=256, kv_quant=args.kv_quant,
                 kv_scheme=args.kv_scheme)
    sconf = ServeConfig(
        batch_slots=args.slots, max_seq=args.max_seq, quantize=scheme,
        kv_layout=args.kv_layout, page_size=args.page_size,
        pool_pages=args.pool_pages, prefill_chunk=args.prefill_chunk,
        kv_cache_dtype=(jnp.bfloat16 if args.kv_dtype == "bf16"
                        else jnp.float32),
        prefix_cache=args.prefix_cache,
        spec_decode=args.spec_decode, spec_k=args.spec_k,
        scheduler=args.scheduler, host_pages=args.host_pages,
        prefix_cache_pages=args.prefix_cache_pages,
        shards=args.shards, replicas=args.replicas).resolve(cfg)
    eng = build_engine(init_params(cfg, args.seed, scheme), cfg, sconf, rt)

    rng = np.random.default_rng(args.seed)
    sys_prompt = (rng.integers(0, cfg.vocab_size, args.shared_prefix)
                  .astype(np.int32))
    # each request must fit shared prefix + tail + new tokens in max_seq
    tail_cap = args.max_seq - args.shared_prefix - args.new_tokens
    if tail_cap < 2:
        raise SystemExit(
            f"--shared-prefix {args.shared_prefix} leaves no room for a "
            f"prompt tail (max-seq {args.max_seq}, new-tokens "
            f"{args.new_tokens})")
    hi = max(2, min(args.max_seq // 4, tail_cap))
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(min(4, hi - 1), hi))
        prompt = np.concatenate(
            [sys_prompt,
             rng.integers(0, cfg.vocab_size, plen).astype(np.int32)])
        reqs.append(Request(rid=i, prompt=prompt,
                            max_new_tokens=args.new_tokens,
                            frames=(None if frame_sets is None
                                    else frame_sets[i % 2])))
    t0 = time.monotonic()
    if args.stream:
        done, delivered = _run_streaming(eng, reqs,
                                         args.arrival_ms / 1e3)
    else:
        for req in reqs:
            eng.submit(req)
        done = eng.run()
    dt = time.monotonic() - t0
    n_tok = sum(len(r.output) for r in done)
    m = eng.metrics()
    # router metrics carry fleet sums; per-engine facts (layout, dtype,
    # pool geometry) live in the untouched per-replica dicts
    m0 = m["per_replica"][0] if sconf.replicas > 1 else m
    print(f"[serve] {len(done)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok / dt:.1f} tok/s), median TTFT {m['ttft_p50_ms']:.0f}ms "
          f"scheme={scheme} layout={m0['kv_layout']} "
          f"kv={m0['kv_scheme']}/{m0['kv_cache_dtype']}")
    if sconf.replicas > 1:
        print(f"[serve] router: {m['replicas']} replicas x {m['shards']} "
              f"shard(s), finished per replica "
              f"{m['requests_per_replica']}, fleet peak KV "
              f"{m['peak_kv_bytes'] / 2**20:.2f} MiB")
    if args.stream:
        sttft = sorted(delivered[r.rid] - r.t_enqueue for r in done)
        print(f"[serve] streaming: delivered TTFT p50 "
              f"{1e3 * sttft[len(sttft) // 2]:.0f}ms over "
              f"{len(done)} consumers (whole-request latency p50 "
              f"{m['latency_p50_ms']:.0f}ms)")
    if sconf.replicas == 1 and m["kv_layout"] == "paged":
        print(f"[serve] pages: {m['n_pages']} x {m['page_size']} tok, "
              f"occupancy mean {m['occupancy_mean']:.2f} / "
              f"peak {m['occupancy_peak']:.2f}, "
              f"peak KV {m['peak_kv_bytes'] / 2**20:.2f} MiB, "
              f"denials {m['admission_denials']}")
        if m["shards"] > 1:
            print(f"[serve] sharded: {m['shards']} shards, kv_sharded="
                  f"{m['kv_sharded']}, {m['kv_heads_per_shard']} KV "
                  f"head(s)/shard, peak KV/shard "
                  f"{m['peak_kv_bytes_per_shard'] / 2**20:.2f} MiB")
        if m["slab_bytes_per_seq"] or m["cross_bytes_per_entry"]:
            print(f"[serve] state cache: peak "
                  f"{m['peak_state_bytes'] / 2**20:.2f} MiB "
                  f"(slabs {m['peak_slabs']} x "
                  f"{m['slab_bytes_per_seq'] / 2**20:.2f} MiB, cross "
                  f"{m['peak_cross']} x "
                  f"{m['cross_bytes_per_entry'] / 2**20:.2f} MiB, "
                  f"{m['cross_hits']}/{m['cross_lookups']} cross hits)")
        if m["scheduler"] == "cb":
            host_cap = ("inf" if m["host_pages"] is None
                        else m["host_pages"])
            print(f"[serve] cb scheduler: {m['preemptions']} preemptions, "
                  f"{m['resumes']} resumes, "
                  f"{m['offload_bytes'] / 2**10:.1f} KiB offloaded, "
                  f"host tier peak {m['peak_host_pages']}/{host_cap} pages")
        if m["prefix_cache"]:
            print(f"[serve] prefix cache: {m['prefix_hits']} hits, "
                  f"{m['prefill_tokens_skipped']} prefill tokens skipped, "
                  f"{m['cow_copies']} COW copies, hit rate "
                  f"{m['prefix_hit_rate']:.2f}, "
                  f"{m['prefix_evictions']} evictions")
        if m["spec_decode"]:
            print(f"[serve] spec decode: K={m['spec_k']}, "
                  f"{m['model_calls']} model calls, "
                  f"{m['accepted_per_step']:.2f} accepted/step, "
                  f"acceptance {m['draft_acceptance_rate']:.2f}")
    print("[serve] metrics: " + json.dumps(m, sort_keys=True))
    return done


if __name__ == "__main__":
    main()
