"""The serving engine's host spans and launch counters
(``repro/serving/spans.py``, docs/SERVING.md "Profiling"): a tiny paged
engine served under ``jax.profiler.start_trace``, its trace read back
with ``jax.profiler.ProfileData``.

* every span of the table appears, nested as documented (launch, wait
  and emit inside prefill or decode, inside the tick), with its args;
* the ``tokens`` args add up to the prompt tokens and decode rows the
  engine served, and ``rows`` is the block the program computes;
* ``metrics()``'s launch counters, prefill row share, queue wait and
  occupancy agree with what the trace and the requests show;
* a running trace changes no output: paged, paged with speculation
  (the verify window through the decode program) or dense.
"""
import dataclasses
import glob
import os

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import lm as lm_mod
from repro.runtime import Runtime
from repro.serving import ServeConfig
from repro.serving.engine import Request, ServeEngine
from repro.serving.spans import SPANS

jax.config.update("jax_platform_name", "cpu")

RT = Runtime(impl="ref", q_chunk=16)
SLOTS = 2
CHUNK = 8
PROMPT_LENS = (5, 13, 20, 3)
NEW_TOKENS = 5

#: the span each span is opened in (None: outside every engine span)
PARENT = {"serve.submit": None, "serve.tick": None,
          "serve.admit": "serve.tick", "serve.prefill": "serve.tick",
          "serve.decode": "serve.tick",
          **{f"serve.{kind}.{part}": f"serve.{kind}"
             for kind in ("prefill", "decode")
             for part in ("launch", "wait", "emit")}}

LAYOUTS = {"fused": dict(kv_layout="paged"),
           "spec": dict(kv_layout="paged", spec_decode=True, spec_k=3),
           "dense": dict(kv_layout="dense")}


def _cfg():
    # vocab 32 keeps random-init top-2 logit gaps wide (exact greedy)
    return dataclasses.replace(reduced(get_config("gemma-2b"), vocab=32),
                               head_dim=128)


def _serve(layout: str, trace_dir=None):
    """(engine, requests, spans) of the prompts served to the end on a
    ``layout`` engine; spans (name, start, end, args) read from the
    trace taken into ``trace_dir``, if given."""
    cfg = _cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(5), cfg)
    eng = ServeEngine(params, cfg,
                      ServeConfig(batch_slots=SLOTS, max_seq=64,
                                  quantize=None, prefill_chunk=CHUNK,
                                  **LAYOUTS[layout]), rt=RT)
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n)
                    .astype(np.int32), max_new_tokens=NEW_TOKENS)
            for i, n in enumerate(PROMPT_LENS)]
    if trace_dir is not None:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        for r in reqs:
            eng.submit(r)
        eng.run()
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
    return eng, reqs, _spans(trace_dir) if trace_dir is not None else []


def _spans(trace_dir) -> list:
    files = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(files) == 1, files
    pd = jax.profiler.ProfileData.from_file(files[0])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     {k: v for k, v in ev.stats})
                    for ev in line.events if ev.name.startswith("serve.")]
    return sorted(out, key=lambda sp: sp[1])


def _parent(spans, sp):
    """Name of the shortest other span that holds ``sp``."""
    holders = [o for o in spans if o is not sp and o[1] <= sp[1]
               and sp[2] <= o[2]]
    return (min(holders, key=lambda o: o[2] - o[1])[0] if holders
            else None)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _serve("fused", tmp_path_factory.mktemp("trace"))


@pytest.mark.parametrize("name", sorted(SPANS))
def test_span_appears_nested_with_its_args(traced, name):
    _, _, spans = traced
    mine = [sp for sp in spans if sp[0] == name]
    assert mine, f"no {name} span in the trace"
    for sp in mine:
        assert set(sp[3]) == set(SPANS[name]), sp
        assert all(isinstance(v, int) and v >= 0 for v in sp[3].values())
        assert _parent(spans, sp) == PARENT[name], sp
    assert {sp[0] for sp in spans} == set(SPANS)


@pytest.mark.parametrize("kind", ["prefill", "decode"])
def test_launch_args_match_what_the_engine_served(traced, kind):
    eng, reqs, spans = traced
    launch = [sp[3] for sp in spans if sp[0] == f"serve.{kind}.launch"]
    wait = [sp[3] for sp in spans if sp[0] == f"serve.{kind}.wait"]
    m = eng.metrics()
    assert len(launch) == len(wait) == m["launches"][kind]
    tokens = sum(a["tokens"] for a in launch)
    rows = sum(a["rows"] for a in launch)
    vocab = eng.cfg.vocab_size
    if kind == "prefill":
        # every prompt token once, in chunks padded to a power of two
        assert tokens == sum(PROMPT_LENS)
        for a in launch:
            assert a["rows"] == SLOTS * a["c_pad"]
            assert a["c_pad"] <= CHUNK
            assert a["c_pad"] & (a["c_pad"] - 1) == 0
            assert 0 < a["tokens"] <= a["rows"]
        assert m["prefill_row_util"] == pytest.approx(tokens / rows)
        assert [a["bytes"] for a in wait] == [SLOTS * vocab * 4] * len(wait)
    else:
        # one decode row per token after each request's first
        assert tokens == sum(len(r.output) - 1 for r in reqs)
        assert [a["rows"] for a in launch] == [SLOTS] * len(launch)
        assert [a["bytes"] for a in wait] == [SLOTS * vocab * 4] * len(wait)


def test_metrics_count_launches_and_queue_wait(traced):
    eng, reqs, spans = traced
    m = eng.metrics()
    assert set(m["launches"]) == {"prefill", "decode"}
    assert m["model_calls"] == sum(m["launches"].values())
    ticks = [sp for sp in spans if sp[0] == "serve.tick"]
    assert len(ticks) == m["engine_steps"]
    for r in reqs:
        assert r.t_enqueue <= r.t_admitted <= r.t_first_token <= r.t_done
    wait = sorted(1e3 * (r.t_admitted - r.t_enqueue) for r in reqs)
    # four requests on two slots: the last two wait for a free one
    assert wait[-1] > 0.0
    assert m["queue_wait_p50_ms"] == pytest.approx(np.median(wait))
    assert m["queue_wait_p50_ms"] <= m["queue_wait_p95_ms"] <= wait[-1]
    # the tick args count the state at the tick's start
    first = ticks[0][3]
    assert first == {"queued": len(reqs), "prefilling": 0, "decoding": 0}
    assert sum(sp[3]["admitted"] for sp in spans
               if sp[0] == "serve.admit") == len(reqs)


@pytest.mark.parametrize("layout", ["paged", "dense"])
def test_occupancy_reads_the_per_tick_samples(layout):
    cfg = _cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(5), cfg)
    eng = ServeEngine(params, cfg, ServeConfig(
        batch_slots=SLOTS, max_seq=64, quantize=None, kv_layout=layout),
        rt=RT)
    for i, n in enumerate(PROMPT_LENS):
        eng.submit(Request(rid=i, prompt=np.arange(1, n + 1, dtype=np.int32)
                           % cfg.vocab_size, max_new_tokens=NEW_TOKENS))
    samples = []
    while eng.has_work():
        eng.step()
        samples.append(eng.pool.stats.occupancy if layout == "paged"
                       else sum(r is not None for r in eng.slot_req) / SLOTS)
    m = eng.metrics()
    assert m["occupancy_mean"] == pytest.approx(float(np.mean(samples)))
    assert m["occupancy_peak"] == float(np.max(samples))
    eng.reset_metrics()
    m = eng.metrics()
    assert m["occupancy_mean"] == m["occupancy_peak"] == 0.0
    assert m["launches"] == {} and m["prefill_row_util"] == 0.0


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_outputs_bit_identical_under_a_trace(layout, tmp_path):
    _, plain, _ = _serve(layout)
    eng, traced, spans = _serve(layout, tmp_path)
    assert [r.output for r in traced] == [r.output for r in plain]
    # every launch of the layout left its spans
    m = eng.metrics()
    assert sum(1 for sp in spans if sp[0].endswith(".launch")) \
        == m["model_calls"]
