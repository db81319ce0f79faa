"""Smoke run of the SPx serving path on a TPU, at the published widths of
qwen2-vl-2b (28 layers, d_model 1536, 12 query / 2 KV heads of 128,
d_ff 8960, vocab 151936, untied head), served on text tokens with random
weights made from ``--seed``.

  python chip_smoke.py                # one chip: every phase below
  python chip_smoke.py --four-chips   # one 2x2 host: the multi-chip paths

Everything runs in this one process, which holds the chip. Phases:

  device     the first device is a TPU whose kind is in the hardware table
             (core/pipeline.HARDWARE); on anything else the script exits
             non-zero and names what it found
  kernels    each main-path Pallas kernel, compiled, against its jnp
             oracle (kernels/ref.py) at the model's shapes
  serve      the engine as ``python -m repro.launch.serve`` builds it
             (sp2_4 weights, paged KV, continuous batching, fused decode,
             8 slots, max_seq 2048) serves 16 seeded requests, once with
             f32 KV and once with spx_8_x3 KV
  reference  the first decode step's logits under Runtime(impl="auto")
             against Runtime(impl="ref"), on the same quantized weights,
             for both KV formats

``--four-chips`` runs only what needs several chips, each against its
one-chip counterpart: ServeConfig(shards=4) against the one-chip logits,
and a ReplicaRouter of four replicas for device placement and greedy
outputs.

A failed phase raises and the script exits non-zero. The last line of
standard output is a JSON object, printed only when every phase passed.
Times here are a smoke run's — one pass, compilation included — and are
not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import spx  # noqa: E402
from repro.core.pipeline import hw_for_kind  # noqa: E402
from repro.core.quantized import quantize_weight  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import build_engine, init_params  # noqa: E402
from repro.models import lm as lm_mod  # noqa: E402
from repro.runtime import Runtime, planner  # noqa: E402
from repro.serving import ReplicaRouter, ServeConfig  # noqa: E402
from repro.serving.engine import Request  # noqa: E402

ARCH = "qwen2-vl-2b"
SCHEME = "sp2_4"
KV_SCHEME = "spx_8_x3"
SLOTS, MAX_SEQ = 8, 2048
N_REQUESTS, NEW_TOKENS = 16, 32

# Tolerances, as relative L2 error ||got - want|| / ||want||. Both sides
# decode the same codebook levels exactly, so they differ only in matmul
# arithmetic: on the MXU an f32 operand may be rounded to bf16 (unit
# roundoff 2^-9 ~ 2e-3) on one side and not the other, which leaves a
# sum of products with random signs about 3e-3 off in relative norm.
KERNEL_RTOL = 1e-2
# The model check compounds that through 28 residual layers of seven
# matmuls each: independent errors of 3e-3 growing like the square root
# of the ~200 matmuls they pass through reach ~4e-2 at the logits.
MODEL_RTOL = 5e-2
# shards=4 runs the same kernels on the same operands; only the order of
# f32 accumulation can change where a contraction is split across chips
# (2^-24 per add, far below this bound even amplified by depth).
SHARD_RTOL = 1e-3


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def check_close(name: str, got, want, rtol: float) -> float:
    err = rel_err(got, want)
    ok = np.all(np.isfinite(np.asarray(got))) and err <= rtol
    log(f"  {name:44s} rel err {err:.3e} (limit {rtol:.0e}) "
        f"{'ok' if ok else 'FAIL'}")
    if not ok:
        raise AssertionError(f"{name}: rel err {err:.3e} > {rtol:.0e} "
                             "or non-finite output")
    return err


class CompileClock:
    """Seconds jax spends tracing, lowering and compiling (or reading a
    compiled program back from the persistent cache) since ``reset``."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def reset(self) -> None:
        self.seconds = 0.0


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def check_device(n_chips: int):
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found platform "
                         f"{d.platform!r} ({d.device_kind}, {len(devs)} "
                         "device(s))")
    hw = hw_for_kind(d.device_kind)          # unknown kind: LookupError
    if len(devs) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} chips, found "
                         f"{len(devs)}")
    log(f"device: {len(devs)} x {d.device_kind} -> {hw.name} "
        f"({hw.source})")
    return d


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _projections(cfg) -> dict:
    """(K, N) of every quantized projection of one layer."""
    d, dh = cfg.d_model, cfg.dh
    return {"wq": (d, cfg.n_heads * dh), "wk/wv": (d, cfg.n_kv_heads * dh),
            "wo": (cfg.n_heads * dh, d), "gate/up": (d, cfg.d_ff),
            "down": (cfg.d_ff, d)}


def kernel_checks(cfg, *, impl: str = "pallas", slots: int = SLOTS,
                  max_seq: int = MAX_SEQ, seed: int = 0) -> None:
    """Each main-path kernel under ``impl`` against its oracle."""
    rng = np.random.default_rng(seed)
    chunk = serve_config(cfg).prefill_chunk
    log(f"kernels ({impl} vs ref):")
    for name, (k, n) in _projections(cfg).items():
        w = jnp.asarray(rng.standard_normal((k, n)) * k ** -0.5, jnp.float32)
        qt = quantize_weight(w, SCHEME)
        assert qt.packed, name
        for m, tick in ((slots, "decode"), (slots * chunk, "prefill")):
            x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
            got = ops.spx_matmul(x, qt, impl=impl)
            want = ops.spx_matmul(x, qt, impl="ref")
            check_close(f"spx_matmul {SCHEME} {name} {tick} "
                        f"({m}x{k}x{n})", got, want, KERNEL_RTOL)

    hkv, hq, dh = cfg.n_kv_heads, cfg.n_heads, cfg.dh
    for kv_scheme, windows in ((None, (1, 5)), (KV_SCHEME, (1, 5))):
        ps = planner.plan_kv_pages(hkv, dh, rep=hq // hkv, act_bytes=4,
                                   kv_scheme=kv_scheme).page_size
        pps = max_seq // ps
        n_pages = slots * pps
        shape = (1, n_pages, hkv, ps, dh)       # one layer's pools
        if kv_scheme is None:
            kp, vp = (jnp.asarray(rng.standard_normal(shape), jnp.float32)
                      for _ in range(2))
        else:
            n_lv = len(spx.scheme_levels(kv_scheme))
            kp, vp = ({"codes": jnp.asarray(rng.integers(0, n_lv, shape),
                                            jnp.uint8),
                       "scale": jnp.asarray(
                           rng.uniform(0.5, 2.0, shape[:3]
                                       + (spx.kv_scale_lanes(ps),)),
                           jnp.float32)} for _ in range(2))
        bt = jnp.asarray(rng.permutation(n_pages).reshape(slots, pps),
                         jnp.int32)
        for w in windows:
            ctx = rng.integers(1, max_seq - w, slots)
            q_len = rng.integers(1, w + 1, slots)
            ctx[-1] = q_len[-1] = 0                  # one idle slot
            q = jnp.asarray(rng.standard_normal((slots, w, hq, dh)),
                            jnp.float32)
            args = (q, kp, vp, bt, jnp.asarray(ctx, jnp.int32),
                    jnp.asarray(q_len, jnp.int32))
            got = ops.paged_decode_ragged(*args, layer=0,
                                          kv_scheme=kv_scheme, impl=impl)
            want = ops.paged_decode_ragged(*args, layer=0,
                                           kv_scheme=kv_scheme, impl="ref")
            name = ("paged_decode_ragged" if kv_scheme is None
                    else f"paged_decode_ragged_quant {kv_scheme}")
            check_close(f"{name} W={w} (page {ps})", got, want,
                        KERNEL_RTOL)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

def serve_config(cfg, *, slots: int = SLOTS, max_seq: int = MAX_SEQ,
                 **kw) -> ServeConfig:
    return ServeConfig(batch_slots=slots, max_seq=max_seq, quantize=SCHEME,
                       kv_layout="paged", scheduler="cb", **kw).resolve(cfg)


def runtime(kv_quant: bool, impl: str = "auto") -> Runtime:
    return Runtime(impl=impl, q_chunk=256, kv_quant=kv_quant,
                   kv_scheme=KV_SCHEME)


def requests(cfg, sconf: ServeConfig, *, n: int = N_REQUESTS,
             lo: int = 128, hi: int = 1024, new_tokens: int = NEW_TOKENS,
             seed: int = 0) -> list[Request]:
    """Seeded prompts of lo..hi tokens. Lengths are whole prefill chunks,
    so every prefill tick runs the one compiled chunk shape."""
    rng = np.random.default_rng(seed)
    c = sconf.prefill_chunk
    lens = rng.integers(lo // c, hi // c + 1, n) * c
    return [Request(rid=i, prompt=rng.integers(1, cfg.vocab_size, int(p))
                    .astype(np.int32), max_new_tokens=new_tokens)
            for i, p in enumerate(lens)]


def serve_phase(params, cfg, *, kv_quant: bool, clock: CompileClock,
                impl: str = "auto", accel: str = "pallas",
                sconf: ServeConfig | None = None,
                req_kw: dict | None = None) -> dict:
    sconf = sconf or serve_config(cfg)
    reqs = requests(cfg, sconf, **(req_kw or {}))
    kv = KV_SCHEME if kv_quant else "f32"
    ops.reset_op_calls()
    clock.reset()
    t0 = time.monotonic()
    eng = build_engine(params, cfg, sconf, runtime(kv_quant, impl))
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    wall = time.monotonic() - t0
    impls = ops.op_impls()
    n_tok = sum(len(r.output) for r in done)
    stats = jax.devices()[0].memory_stats() or {}
    m = eng.metrics()
    log(f"serve, KV {kv}: {len(done)} requests "
        f"(prompts {min(len(r.prompt) for r in reqs)}.."
        f"{max(len(r.prompt) for r in reqs)} tok), {n_tok} tokens out")
    log(f"  op -> impl: " + ", ".join(
        f"{op}->{im} x{c}" for (op, im), c in sorted(impls.items())))
    log(f"  compile {clock.seconds:.1f} s, wall {wall:.1f} s (compile "
        f"included), peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB, "
        f"{m['preemptions']} preemptions, page {m['page_size']} tok")
    fallbacks = {k: c for k, c in impls.items() if k[1] != accel}
    if fallbacks:
        raise AssertionError(f"ops that did not run {accel}: {fallbacks}")
    need = {"spx_matmul",
            "paged_decode_ragged_quant" if kv_quant
            else "paged_decode_ragged"}
    missing = need - {op for op, _ in impls}
    if missing:
        raise AssertionError(f"main-path ops never called: {missing}")
    if len(done) != len(reqs) or n_tok != len(reqs) * reqs[0].max_new_tokens:
        raise AssertionError(f"{len(done)}/{len(reqs)} requests, {n_tok} "
                             "tokens")
    return {r.rid: list(r.output) for r in done}


# ---------------------------------------------------------------------------
# reference: first decode step, model-level, on an engine's weights/caches
# ---------------------------------------------------------------------------

_STEP = jax.jit(lm_mod.lm_paged_step, static_argnums=(7, 8))
_FUSED = jax.jit(lm_mod.lm_paged_fused_step, static_argnums=(7, 8))


def _tick_args(eng, b: int):
    """One sequence per slot on its own pages; no slab or cross entry
    (pure-attention pattern: the sentinels are the region sizes, 0)."""
    pps = eng.pages_per_seq
    bt = np.arange(b * pps, dtype=np.int32).reshape(b, pps)
    return bt, np.zeros((b, 2), np.int32)


def prefill(eng, prompts: np.ndarray):
    """Feed (B, P) prompts through ``eng``'s weights and a copy of its
    page pools in prefill chunks; returns (last-token logits, caches)."""
    b, p = prompts.shape
    c = eng.prefill_chunk
    bt, si = _tick_args(eng, b)
    caches = eng.caches
    for c0 in range(0, p, c):
        logits, caches = _STEP(eng.params, prompts[:, c0:c0 + c],
                               np.full(b, c0, np.int32), bt,
                               np.full(b, c, np.int32), si, caches,
                               eng.cfg, eng.rt)
    return logits, caches


def decode_one(eng, caches, tok: np.ndarray, pos: int):
    """One fused decode tick of ``tok`` (B,) at position ``pos``."""
    b = tok.shape[0]
    bt, si = _tick_args(eng, b)
    logits, _ = _FUSED(eng.params, tok[:, None].astype(np.int32),
                       np.full(b, pos, np.int32), bt, np.ones(b, np.int32),
                       si, caches, eng.cfg, eng.rt)
    return logits[:, 0]


def first_decode_logits(engines: dict, prompts: np.ndarray, base: str):
    """Prefill + one decode tick on every engine. Every engine decodes
    the token ``base``'s prefill picked, so the decode logits compare on
    identical inputs. Returns {name: (prefill logits, decode logits)}."""
    pre = {k: prefill(e, prompts) for k, e in engines.items()}
    tok = np.asarray(jnp.argmax(pre[base][0], axis=-1))
    return {k: (np.asarray(pre[k][0]),
                np.asarray(decode_one(e, pre[k][1], tok, prompts.shape[1])))
            for k, e in engines.items()}


def _agreement(a: np.ndarray, b: np.ndarray) -> str:
    same = int(np.sum(np.argmax(a, -1) == np.argmax(b, -1)))
    return f"{same}/{a.shape[0]}"


def reference_phase(params, cfg, *, kv_quant: bool, impl: str = "auto",
                    sconf: ServeConfig | None = None, prompt_len: int = 128,
                    seed: int = 0) -> None:
    sconf = sconf or serve_config(cfg)
    rng = np.random.default_rng(seed + 1)
    prompts = rng.integers(1, cfg.vocab_size,
                           (sconf.batch_slots, prompt_len)).astype(np.int32)
    engines = {k: build_engine(params, cfg, sconf, runtime(kv_quant, k))
               for k in (impl, "ref")}
    out = first_decode_logits(engines, prompts, base="ref")
    kv = KV_SCHEME if kv_quant else "f32"
    log(f"reference, KV {kv}: Runtime(impl={impl!r}) vs "
        f"Runtime(impl='ref'), {prompts.shape[0]} x {prompt_len}-token "
        f"prompts")
    check_close(f"prefill logits ({out['ref'][0].shape[-1]} vocab)",
                out[impl][0], out["ref"][0], MODEL_RTOL)
    check_close("first decode step logits", out[impl][1], out["ref"][1],
                MODEL_RTOL)
    log(f"  greedy-token agreement: prefill "
        f"{_agreement(out[impl][0], out['ref'][0])}, first decode step "
        f"{_agreement(out[impl][1], out['ref'][1])}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def _hlo_summary(hlo: str) -> str:
    """Collective counts and the all-gathered u8 (weight code) shapes of
    a compiled program."""
    import re
    counts = {op: len(re.findall(rf"\b{op}(?:-start)?\(", hlo))
              for op in ("all-gather", "all-reduce", "reduce-scatter",
                         "all-to-all", "collective-permute")}
    counts["tpu_custom_call"] = hlo.count('custom_call_target="tpu_custom_call"')
    gathered = sorted(set(re.findall(
        r"= (u8\[[0-9,]+\])[^=]*? all-gather(?:-start)?\(", hlo)))
    return (", ".join(f"{k} {v}" for k, v in counts.items())
            + f"; all-gathered u8 codes: {gathered or 'none'}")


def four_chip_phase(params, cfg, *, kv_quant: bool = False,
                    impl: str = "auto", sconf: ServeConfig | None = None,
                    prompt_len: int = 128, new_tokens: int = 8,
                    seed: int = 0) -> None:
    devs = jax.devices()[:4]
    sconf = sconf or serve_config(cfg)
    rt = runtime(kv_quant, impl)
    rng = np.random.default_rng(seed + 2)
    prompts = rng.integers(1, cfg.vocab_size,
                           (sconf.batch_slots, prompt_len)).astype(np.int32)

    one = build_engine(params, cfg, sconf, rt)
    sharded = build_engine(params, cfg, sconf.replace(shards=4), rt)
    out = first_decode_logits({"one": one, "shards4": sharded}, prompts,
                              base="one")
    log(f"shards=4 vs one chip ({prompts.shape[0]} x {prompt_len}-token "
        f"prompts):")
    check_close("prefill logits", out["shards4"][0], out["one"][0],
                SHARD_RTOL)
    check_close("first decode step logits", out["shards4"][1],
                out["one"][1], SHARD_RTOL)
    log(f"  greedy-token agreement: prefill "
        f"{_agreement(out['shards4'][0], out['one'][0])}, first decode "
        f"step {_agreement(out['shards4'][1], out['one'][1])}")
    b = prompts.shape[0]
    bt, si = _tick_args(sharded, b)
    hlo = _FUSED.lower(sharded.params, np.zeros((b, 1), np.int32),
                       np.zeros(b, np.int32), bt, np.ones(b, np.int32), si,
                       sharded.caches, cfg, sharded.rt).compile().as_text()
    log(f"  shards=4 fused decode step HLO: {_hlo_summary(hlo)}")
    del sharded

    router = ReplicaRouter(params, cfg, sconf.replace(replicas=4), rt=rt)
    placed = []
    for i, eng in enumerate(router.engines):
        held = set()
        for leaf in jax.tree_util.tree_leaves(eng.params):
            held |= leaf.devices()
        placed.append(held)
        if held != {devs[i]}:
            raise AssertionError(f"replica {i} params on {held}, want "
                                 f"{{{devs[i]}}}")
    log(f"router: replica i's params on device i: "
        f"{[sorted(d.id for d in h) for h in placed]}")
    reqs = [Request(rid=i, prompt=p, max_new_tokens=new_tokens)
            for i, p in enumerate(prompts)]
    for r in reqs:
        router.submit(r)
    fleet = {r.rid: list(r.output) for r in router.run()}
    for r in [Request(rid=i, prompt=p, max_new_tokens=new_tokens)
              for i, p in enumerate(prompts)]:
        one.submit(r)
    solo = {r.rid: list(r.output) for r in one.run()}
    same = sum(fleet[i] == solo[i] for i in solo)
    log(f"  router greedy outputs equal to one engine's: {same}/"
        f"{len(solo)} requests, per replica "
        f"{router.metrics()['requests_per_replica']}")
    if same != len(solo):
        raise AssertionError("router outputs differ from one engine's")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths (shards=4, four "
                         "replicas) against their one-chip counterparts")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    cache_dir = enable_compile_cache()
    n_chips = 4 if args.four_chips else 1
    dev = check_device(n_chips)
    clock = CompileClock()
    log("smoke run: one pass, compilation included — not a benchmark")
    log(f"compile cache: {cache_dir}")

    cfg = get_config(ARCH)
    if not args.four_chips:
        kernel_checks(cfg, seed=args.seed)
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads}/{cfg.n_kv_heads} heads x {cfg.dh}, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab_size}, tied head "
        f"{cfg.tie_embeddings}")
    t0 = time.monotonic()
    params = init_params(cfg, args.seed, SCHEME)
    jax.block_until_ready(params)
    stats = dev.memory_stats() or {}
    log(f"weights: random from seed {args.seed}, {SCHEME}-quantized in "
        f"{time.monotonic() - t0:.1f} s, peak_bytes_in_use "
        f"{stats.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB")

    if args.four_chips:
        four_chip_phase(params, cfg, seed=args.seed)
    else:
        for kv_quant in (False, True):
            serve_phase(params, cfg, kv_quant=kv_quant, clock=clock)
        for kv_quant in (False, True):
            reference_phase(params, cfg, kv_quant=kv_quant, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
