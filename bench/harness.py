"""The benchmark's core: find a cell by name, build it, warm it, drive its
traffic for the window, check what it served, and assemble the result.

Everything that belongs to one configuration, traffic mix, per-layer
metric or kernel lives in a file of its own under ``bench/``, found by
the name ``BENCHMARK.json`` gives:

  bench/configs/<config>.json    sizes, weight and KV formats, serving
                                 shape, the model module, the limit of
                                 the correctness comparison
  bench/models/<model>.py        plain reference, kernel calls, FLOPs,
                                 and the layout of the program's weights
  bench/traffic/<traffic>.json   a mix for ``traffic.generate``
  bench/metrics/<metric>.py      compute(rec) -> number or None
  bench/work/<kernel>.py         MATCH (trace names) and work(call) ->
                                 (flops, bytes)
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import re
import shutil
import sys
import time
from pathlib import Path

import numpy as np

import spx_ref
import traffic
import xplane

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"

#: requests in the correctness sample, and served tokens compared in each
SAMPLE_REQUESTS = 8
TOKENS_PER_REQUEST = 48


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# -- discovery ----------------------------------------------------------------

def load_spec(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def plugin(kind: str, name: str, bench: Path = BENCH):
    """The module ``bench/<kind>/<name>.py``."""
    path = bench / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict             # the configuration file
    mix: dict                # the traffic file
    end_to_end: list         # metric entries of BENCHMARK.json it reports
    per_layer: list


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def find_cell(spec: dict, name: str, root: Path = ROOT) -> Cell:
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    with open(root / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        mix = json.load(f)
    return Cell(name, w["chips"], config, mix,
                [m for m in spec["end_to_end"] if reports(m, name)],
                [m for m in spec["per_layer"] if reports(m, name)])


# -- device -------------------------------------------------------------------

def check_device(chips: int, peaks: dict):
    """The devices, if they are TPUs of a kind in the peak table and at
    least ``chips`` of them; otherwise SystemExit (no result printed)."""
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, found {d.platform!r} "
                         f"({d.device_kind}, {len(devs)} device(s))")
    if d.device_kind not in peaks:
        raise SystemExit(f"bench: no peaks for device kind "
                         f"{d.device_kind!r}; bench/peaks.json has "
                         f"{sorted(peaks)}")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, found "
                         f"{len(devs)}")
    return devs


def enable_cache(cache_dir: Path = CACHE_DIR) -> None:
    """JAX's persistent compilation cache in the directory
    ``JAX_COMPILATION_CACHE_DIR`` names, else at a fixed path in the
    checkout, keeping every program so a second run compiles nothing."""
    import jax
    cache_dir = os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                                      str(cache_dir))
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Backend compilations (or cache loads) and traces since start."""
    def __init__(self):
        import jax
        self.compiles = 0
        self.traces = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.seconds += duration
        elif event == "/jax/core/compile/jaxpr_trace_duration":
            self.traces += 1
            self.seconds += duration


# -- the system under test ----------------------------------------------------

def model_module(config: dict):
    return plugin("models", config["model"])


def arch_config(config: dict):
    """The program's ArchConfig for this configuration file: the repo's
    entry for ``arch`` with the file's published sizes laid over it."""
    from repro.configs import get_config
    c = config
    if c.get("rms_norm_eps", 1e-6) != 1e-6:
        raise ValueError("the program's RMSNorm epsilon is 1e-6")
    mrope = c.get("rope_scaling", {}).get("mrope_section")
    return dataclasses.replace(
        get_config(c["arch"]),
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c["hidden_size"] // c["num_attention_heads"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=bool(c["tie_word_embeddings"]),
        mrope_sections=tuple(mrope) if mrope else None)


@dataclasses.dataclass
class Served:
    front: object            # ServeEngine or ReplicaRouter
    engines: list


def build(config: dict, seed: int, impl: str = "auto") -> Served:
    """Weights from ``seed`` (made on the device in one jitted call and
    quantized by the program's own step) and the serving front end as
    ``repro.launch.serve.build_engine`` builds it. ``impl`` is the
    program's kernel choice (CPU tests run ``interpret``)."""
    from repro.launch.serve import build_engine
    from repro.nn.layers import quantize_params
    from repro.runtime import Runtime
    from repro.serving import ServeConfig
    arch = arch_config(config)
    params = model_module(config).program_params(
        config, seed, lambda t: quantize_params(t, config["weights"]))
    kv = config["kv"]
    rt = Runtime(impl=impl, q_chunk=256, kv_quant=kv != "f32",
                 kv_scheme=kv if kv != "f32" else "uniform8")
    sconf = ServeConfig(
        batch_slots=config["batch_slots"], max_seq=config["max_seq"],
        quantize=config["weights"], kv_layout="paged", scheduler="cb",
        fused_decode=True, prefix_cache=False, spec_decode=False,
        replicas=config.get("replicas", 1), seed=0).resolve(arch)
    front = build_engine(params, arch, sconf, rt)
    engines = getattr(front, "engines", [front])
    return Served(front, engines)


def warm(served: Served, prefill: bool = True) -> None:
    """Compile every shape the window can use, on every replica: the
    prefill step at each power-of-two chunk pad up to the chunk (unless
    the window runs no prefill), and the fused decode tick (W=1)."""
    from repro.serving.engine import Request
    rid = 2 ** 30
    for eng in served.engines:
        c = 1 if prefill else eng.prefill_chunk
        while True:
            eng.submit(Request(rid=rid, prompt=np.ones(c, np.int32),
                               max_new_tokens=2))
            eng.run()
            rid += 1
            if c >= eng.prefill_chunk:
                break
            c = min(2 * c, eng.prefill_chunk)
        eng.reset_metrics()


def bring_up(cell: Cell, seed: int, impl: str = "auto") -> Served:
    """The cell's front end with weights from ``seed``, every shape its
    traffic uses compiled."""
    served = build(cell.config, seed, impl)
    warm(served, prefill=not cell.mix.get("fill_before_window"))
    return served


def drive(served: Served, cell: Cell, seed: int, seconds: float, *,
          mix: dict | None = None, annotate=None) -> "Load":
    """The Load that sends the cell's traffic (``mix`` in place of the
    cell's, where given) for a window of ``seconds``; in a mix that fills
    the slots before the window, driven until every request decodes."""
    mix = mix or cell.mix
    config = cell.config
    plan = traffic.generate(
        mix, seed, seconds=seconds,
        clients=config["batch_slots"] * config.get("replicas", 1)
        * mix.get("clients_per_slot", 1),
        max_seq=config["max_seq"], vocab=config["vocab_size"])
    drv = Load(served, mix, plan, annotate=annotate)
    if mix.get("fill_before_window"):
        drv.run_until(time.monotonic(), math.inf, until_all_decoding=True)
    return drv


# -- the measured window ------------------------------------------------------

@dataclasses.dataclass
class Track:
    req: object
    due: float
    admitted: float | None = None
    times: list = dataclasses.field(default_factory=list)
    failed: bool = False


@dataclasses.dataclass
class Tick:
    t0: float
    t1: float


class Load:
    """Drives one front end with a traffic plan. Times are
    ``time.monotonic`` seconds; a token's time is the end of the tick
    that emitted it."""

    def __init__(self, served: Served, mix: dict, plan, *, annotate=None):
        self.served = served
        self.mix = mix
        self.tracks: dict[int, Track] = {}
        self.ticks: list[Tick] = []
        self.live: dict[int, Track] = {}
        self.next_rid = 0
        self.annotate = annotate or (lambda name: contextlib.nullcontext())
        self.late_s = 0.0
        if mix["loop"] == "open":
            self.pending = list(plan)
        else:
            self.clients = [list(reversed(q)) for q in plan]
            self.client_of: dict[int, int] = {}
            self.busy: set[int] = set()

    def _submit(self, item, due: float, client: int | None = None):
        from repro.serving.engine import Request
        req = Request(rid=self.next_rid, prompt=item.prompt,
                      max_new_tokens=item.max_new_tokens)
        self.next_rid += 1
        tr = Track(req, due)
        self.tracks[req.rid] = tr
        try:
            with self.annotate("bench.submit"):
                self.served.front.submit(req)
        except Exception as e:            # refused: counts as failed
            log(f"submit of request {req.rid} failed: {e}")
            tr.failed = True
            return
        self.live[req.rid] = tr
        if client is not None:
            self.client_of[req.rid] = client
            self.busy.add(client)

    def _feed(self, now: float, t_start: float) -> float | None:
        """Submit whatever is due; returns the next due time (or None)."""
        if self.mix["loop"] == "open":
            while self.pending and t_start + self.pending[0].due <= now:
                it = self.pending.pop(0)
                due = t_start + it.due
                self.late_s = max(self.late_s, now - due)
                self._submit(it, due)
            return t_start + self.pending[0].due if self.pending else None
        for i, q in enumerate(self.clients):
            if i not in self.busy and q:
                self._submit(q.pop(), now, client=i)
        return None

    def _tick(self) -> None:
        t0 = time.monotonic()
        with self.annotate("bench.step"):
            self.served.front.step()
        t1 = time.monotonic()
        held = {r.rid for e in self.served.engines for r in e.slot_req
                if r is not None}
        for rid, tr in list(self.live.items()):
            if tr.admitted is None and rid in held:
                tr.admitted = t0
            n_new = len(tr.req.output) - len(tr.times)
            if n_new > 0:
                tr.times.extend([t1] * n_new)
                if tr.admitted is None:
                    tr.admitted = t0
            if tr.req.done:
                del self.live[rid]
                if self.mix["loop"] == "closed":
                    self.busy.discard(self.client_of.pop(rid))
        self.ticks.append(Tick(t0, t1))

    def run_until(self, t_start: float, t_end: float,
                  until_all_decoding: bool = False) -> None:
        """Feed and tick until ``t_end`` (or, with ``until_all_decoding``,
        until every live request has its first token)."""
        while True:
            now = time.monotonic()
            if now >= t_end:
                return
            nxt = self._feed(now, t_start)
            if until_all_decoding and self.live and all(
                    tr.times for tr in self.live.values()):
                return
            if self.served.front.has_work():
                self._tick()
            else:
                wake = t_end if nxt is None else min(nxt, t_end)
                time.sleep(max(0.0, wake - time.monotonic()))


# -- results ------------------------------------------------------------------

def p95(values) -> float | None:
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), 95))


def window_record(drv: Load, t_open: float, t_close: float) -> dict:
    """What the metrics read: per-request due and token times
    inside the window, and the ticks of the window."""
    ttft, itl, tokens, attempted, failed = [], [], 0, 0, 0
    for tr in drv.tracks.values():
        in_window = [t for t in tr.times if t_open <= t < t_close]
        tokens += len(in_window)
        itl.extend(b - a for a, b in zip(in_window, in_window[1:]))
        due_in = t_open <= tr.due < t_close
        resident = tr.due < t_open and (not tr.req.done
                                        or tr.times[-1] >= t_open)
        if due_in or (drv.mix["loop"] == "closed" and resident
                      and not tr.failed):
            attempted += 1
        if tr.failed and due_in:
            failed += 1
        if due_in and not tr.failed:
            first = tr.times[0] if tr.times and tr.times[0] < t_close \
                else t_close
            ttft.append(first - tr.due)
    ticks = [t for t in drv.ticks if t_open <= t.t0 and t.t1 <= t_close]
    return {"window_s": t_close - t_open, "tokens": tokens,
            "ttft_s": ttft, "itl_s": itl,
            "attempted": attempted, "failed": failed, "ticks": ticks,
            "generator_late_s": drv.late_s}


def end_to_end(rec: dict, setup_s: float) -> dict:
    out = {"output_tok_per_s": rec["tokens"] / rec["window_s"],
           "setup_s": setup_s}
    if rec["ttft_s"]:
        out["ttft_p95_ms"] = 1e3 * p95(rec["ttft_s"])
    if rec["itl_s"]:
        out["itl_p95_ms"] = 1e3 * p95(rec["itl_s"])
    return out


def kernel_work(drv: Load, config: dict, t_open: float,
                t_close: float) -> dict:
    """The kernel calls the window's ticks made, from what the benchmark
    saw of each request: decode rows with their attended lengths, and
    prompt tokens spread evenly over the ticks from admission to first
    token (counted where those ticks lie in the window). ->
    {"spx_matmul": [call, ...], "paged_attention": [call, ...],
    "flops": model FLOPs of the useful work}."""
    mm = model_module(config)
    starts = np.array([t.t0 for t in drv.ticks])
    in_win = np.array([t_open <= t.t0 and t.t1 <= t_close
                       for t in drv.ticks], bool)
    n = len(drv.ticks)
    prefill_rows = np.zeros(n)
    prefill_attended = np.zeros(n)
    decode: list[list[int]] = [[] for _ in range(n)]
    head_rows = 0

    def tick_of(t: float) -> int:
        return int(np.searchsorted(starts, t, side="right")) - 1

    for tr in drv.tracks.values():
        if not tr.times:
            continue
        p = len(tr.req.prompt)
        for j, t in enumerate(tr.times):
            if t_open <= t < t_close:
                head_rows += 1
                if j > 0:
                    decode[tick_of(t)].append(p + j)
        lo, hi = tick_of(tr.admitted), tick_of(tr.times[0])
        for i in range(lo, hi + 1):
            prefill_rows[i] += p / (hi - lo + 1)
            prefill_attended[i] += p * p / 2 / (hi - lo + 1)
    calls_mm, calls_attn = [], []
    d = mm.dims(config)
    for i in np.flatnonzero(in_win):
        for m in (prefill_rows[i], len(decode[i])):
            if m > 0:
                calls_mm += [{"m": float(m), "k": k, "n": nn,
                              "bits": weight_bits(config),
                              "layers": d["layers"]}
                             for _, k, nn in mm.matmuls(config)]
        if decode[i]:
            calls_attn.append({"attended": [float(a) for a in decode[i]],
                               "hq": d["hq"], "hkv": d["hkv"],
                               "dh": d["dh"], "kv": config["kv"],
                               "layers": d["layers"]})
    tok = float(prefill_rows[in_win].sum()) + sum(
        len(decode[i]) for i in np.flatnonzero(in_win))
    attended = float(prefill_attended[in_win].sum()) + sum(
        sum(decode[i]) for i in np.flatnonzero(in_win))
    flops = (tok * mm.token_flops(config) + head_rows * mm.head_flops(config)
             + mm.attention_flops(config, attended))
    return {"spx_matmul": calls_mm, "paged_attention": calls_attn,
            "flops": flops}


def weight_bits(config: dict) -> int:
    """Bits of one stored weight code of the configuration's scheme."""
    return int(math.ceil(math.log2(len(spx_ref.levels(config["weights"])))))


def roofline(kernel: str, calls: list, trace: dict, peak: dict):
    """(share in %, bound) of ``kernel``: the least time of the calls the
    window made over the device time of the kernel's trace events; None
    where the trace holds none of its events or no call was made."""
    mod = plugin("work", kernel)
    pats = [re.compile(p) for p in mod.MATCH]
    dev = sum(d for name, stats, d in trace["events"]
              if any(p.search(name) or p.search(str(stats.get(
                  "long_name", ""))) or p.search(str(stats.get(
                      "tf_op", ""))) for p in pats))
    dev /= max(1, trace["n_devices"])
    if dev <= 0 or not calls:
        return None, None
    t_flops = t_bytes = 0.0
    least = 0.0
    for call in calls:
        flops, nbytes = mod.work(call)
        a = flops / peak["bf16_flops_per_s"]
        b = nbytes / peak["hbm_bytes_per_s"]
        least += max(a, b)
        t_flops += a
        t_bytes += b
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * least / dev, bound


def sample_for_check(drv: Load, seed: int) -> list[Track]:
    """Up to ``SAMPLE_REQUESTS`` requests that served tokens, drawn from
    the seed, the longest among them."""
    served = [tr for tr in drv.tracks.values() if tr.req.output]
    if not served:
        return []
    rng = np.random.default_rng(seed + 7)
    longest = max(served, key=lambda t: len(t.req.prompt)
                  + len(t.req.output))
    rest = [served[i] for i in rng.permutation(len(served))
            if served[i] is not longest]
    return [longest] + rest[:SAMPLE_REQUESTS - 1]


def served_rows(sample: list[Track]):
    """(sequences, (sequence, position) rows, served tokens): of each
    sampled request, up to ``TOKENS_PER_REQUEST`` served tokens spread
    evenly from its first (chosen by prefill) to its last (the longest
    context), the position whose logits chose each, and the prompt with
    the served tokens before the last of them."""
    seqs, rows, toks = [], [], []
    for i, tr in enumerate(sample):
        p, o = np.asarray(tr.req.prompt), np.asarray(tr.req.output, np.int32)
        js = np.unique(np.linspace(0, len(o) - 1, min(len(o),
                                   TOKENS_PER_REQUEST)).round().astype(int))
        seqs.append(np.concatenate([p, o[:js[-1]]]))
        rows += [(i, len(p) - 1 + int(j)) for j in js]
        toks += [int(o[j]) for j in js]
    return seqs, rows, np.asarray(toks)


def reference(config: dict, seed: int, seqs, rows, precision: str = "f32"):
    """The plain reference's logits at ``rows``, as float64. Its programs
    see one shape per cell (``SAMPLE_REQUESTS`` sequences of ``max_seq``,
    rows padded to a power of two), so every run after the first finds
    them in the compile cache."""
    kv = None if config["kv"] == "f32" else config["kv"]
    seqs = list(seqs) + [np.zeros(1, np.int32)] * (SAMPLE_REQUESTS
                                                   - len(seqs))
    n = len(rows)
    padded = list(rows) + [(0, 0)] * ((1 << (n - 1).bit_length()) - n)
    return np.asarray(model_module(config).reference_logits(
        config, seed, seqs, padded, weight_scheme=config["weights"],
        kv_scheme=kv, precision=precision, length=config["max_seq"]),
        np.float64)[:n]


def check(drv: Load, config: dict, seed: int, controls=()) -> dict:
    """The numbers compared, each with its limit, and whether all hold.
    Each of ``controls`` (a precision of the reference: "bf16", "fp8")
    is judged the same way in the program's place: at the same positions
    of the same prompts and served tokens, the token it ranks first."""
    sample = sample_for_check(drv, seed)
    lim = config["limits"]
    if not sample:
        verdict = {"correct": False,
                   "compared": {"served_tokens": {"value": 0, "limit": 1}}}
        return dict(verdict, controls={p: verdict for p in controls})
    seqs, rows, toks = served_rows(sample)
    ref = reference(config, seed, seqs, rows)
    short = sum(1 for tr in drv.tracks.values() if tr.req.done
                and len(tr.req.output) != tr.req.max_new_tokens)

    def judge(picked) -> dict:
        gaps = ref.max(-1) - ref[np.arange(len(picked)), picked]
        ok = (np.isfinite(gaps).all() and gaps.max() <= lim["logit_gap_max"]
              and short == 0)
        return {"correct": bool(ok), "compared": {
            "logit_gap_max": {"value": float(gaps.max()),
                              "limit": lim["logit_gap_max"]},
            "short_outputs": {"value": short, "limit": 0}}}

    log(f"checked {len(toks)} served tokens of {len(sample)} requests")
    verdict = judge(toks)
    verdict["controls"] = {
        p: judge(reference(config, seed, seqs, rows, p).argmax(-1))
        for p in controls}
    return verdict


# -- one run ------------------------------------------------------------------

def memory_peak(devs) -> int:
    peaks = []
    for d in devs:
        st = d.memory_stats() or {}
        peaks.append(int(st.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_process: float, devs, peaks: dict, bench: Path = BENCH,
             impl: str = "auto", controls=()) -> dict:
    """One run of ``cell``: returns the result object (the last line).
    ``controls``: precisions of the reference to judge in the program's
    place as well (``check``); the benchmark's own runs judge none."""
    import jax
    counter = CompileCounter()
    config, mix = cell.config, cell.mix
    t0 = time.monotonic()
    served = bring_up(cell, seed, impl)
    log(f"weights, engine and warm-up {time.monotonic() - t0:.3f} s, "
        f"{counter.compiles} compiles ({counter.seconds:.3f} s tracing and "
        f"compiling), device peak "
        f"{memory_peak(devs[:cell.chips]) / 2**30:.3f} GiB")
    annotate = (jax.profiler.TraceAnnotation if trace
                else (lambda name: contextlib.nullcontext()))
    t1 = time.monotonic()
    drv = drive(served, cell, seed, seconds, annotate=annotate)
    if mix.get("fill_before_window"):
        log(f"slots filled in {time.monotonic() - t1:.3f} s")
    t_start = time.monotonic()
    t_open = t_start + mix.get("warmup_s", 0.0)
    drv.run_until(t_start, t_open)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        t_open = time.monotonic()
    compiles0 = counter.compiles + counter.traces
    setup_s = t_open - t_process
    t_close = t_open + seconds
    if trace:
        with jax.profiler.TraceAnnotation("bench.open"):
            pass
    drv.run_until(t_start, t_close)
    if trace:
        with jax.profiler.TraceAnnotation("bench.close"):
            pass
        jax.profiler.stop_trace()
    window_compiles = counter.compiles + counter.traces - compiles0
    t_close = min(t_close, time.monotonic())
    mem = memory_peak(devs[:cell.chips])
    rec = window_record(drv, t_open, t_close)
    log(f"window {rec['window_s']:.3f} s: {len(rec['ticks'])} ticks, "
        f"{rec['tokens']} tokens, {rec['attempted']} attempted, "
        f"{rec['failed']} failed, generator late by at most "
        f"{rec['generator_late_s'] * 1e3:.1f} ms, {window_compiles} "
        f"compiles in the window, set-up {setup_s:.3f} s")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    result: dict = {"correct": False, "attempted": rec["attempted"],
                    "failed": rec["failed"]}
    peak = peaks[devs[0].device_kind]
    if trace:
        ops, spans = xplane.load(str(TRACE_DIR))
        lo = min(s for n, s, _ in spans if n == "bench.open")
        hi = max(s for n, s, _ in spans if n == "bench.close")
        red = xplane.reduce(ops, spans, lo, hi)
        work = kernel_work(drv, config, t_open, t_close)
        prec = {"rec": rec, "trace": red, "work": work, "config": config,
                "chips": cell.chips, "peak": peak,
                "window_compiles": window_compiles, "roofline": roofline}
        metrics = {}
        for m in cell.per_layer:
            v = plugin("metrics", m["name"], bench).compute(prec)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        e2e = end_to_end(rec, setup_s)
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in e2e}
    # free the program's state before the reference runs
    served.engines.clear()
    del served
    drv.served = None
    gc.collect()
    t_ref = time.monotonic()
    verdict = check(drv, config, seed, controls)
    log(f"reference check took {time.monotonic() - t_ref:.1f} s, device "
        f"peak after it {memory_peak(devs[:cell.chips]) / 2**30:.3f} GiB")
    result.update(correct=verdict["correct"], metrics=metrics, device=device)
    if controls:
        result["controls"] = verdict["controls"]
    result["compared"] = verdict["compared"]
    return result


def print_result(result: dict) -> None:
    for prec, verdict in result.get("controls", {}).items():
        for name, v in verdict["compared"].items():
            print(f"control {prec} {name}: {v['value']} (limit "
                  f"{v['limit']})", file=sys.stderr, flush=True)
    for name, v in result["compared"].items():
        if isinstance(v, dict):
            print(f"compared {name}: {v['value']} (limit {v['limit']})",
                  file=sys.stderr, flush=True)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
