"""CPU tests of the per-layer metrics that read the serving program's own
spans (bench/program_spans.py): the window cut and busy intervals of a
synthetic trace, each metric's hand-computed value, nothing read from a
program without the spans, and a tiny traced cell end to end.

  python -m pytest bench -q
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import harness  # noqa: E402
import program_spans  # noqa: E402
import test_harness  # noqa: E402

S = 1e9   # ns a second


def _tick(t0, t1, prefill, p_args, decode, d_args):
    """One engine tick's spans: prefill and decode, each (start, end of
    launch, end of wait, end) in seconds, with their launch args."""
    out = [("serve.tick", t0 * S, t1 * S, {"queued": 0, "prefilling": 1,
                                           "decoding": 1})]
    for kind, (a, b, c, d), args in (("prefill", prefill, p_args),
                                     ("decode", decode, d_args)):
        out += [(f"serve.{kind}", a * S, d * S, {}),
                (f"serve.{kind}.launch", a * S, b * S, args),
                (f"serve.{kind}.wait", b * S, c * S, {"bytes": 8}),
                (f"serve.{kind}.emit", c * S, d * S, {})]
    return out


def _synthetic():
    """Ops on two devices; two ticks inside the window [0, 10) s, one
    tick after it."""
    device_ops = {0: [(0, 3 * S), (1 * S, 2 * S), (6 * S, 7 * S),
                      (9.5 * S, 11 * S)],
                  1: [(0, 1 * S)]}
    host = ([("bench.open", 0, 0, {}), ("bench.close", 10 * S, 10 * S, {}),
             ("bench.step", 0, 5 * S, {})]
            + _tick(0.5, 4.5, (0.6, 0.8, 1.9, 2.0),
                    {"rows": 64, "tokens": 16, "c_pad": 16},
                    (2.0, 2.1, 4.2, 4.4), {"rows": 4, "tokens": 3})
            + _tick(5.6, 8.8, (5.7, 5.8, 5.95, 6.0),
                    {"rows": 64, "tokens": 48, "c_pad": 16},
                    (6.0, 6.1, 8.6, 8.7), {"rows": 4, "tokens": 4})
            + _tick(10.5, 12.0, (10.6, 10.7, 10.8, 10.9),
                    {"rows": 64, "tokens": 1, "c_pad": 1},
                    (10.9, 11.0, 11.8, 11.9), {"rows": 4, "tokens": 1}))
    return device_ops, host


def test_window_keeps_the_program_spans_inside_it():
    ps = program_spans.window(*_synthetic())
    names = [n for n, *_ in ps["spans"]]
    assert names.count("serve.tick") == 2          # the third lies after
    assert all(n.startswith("serve.") for n in names)
    assert [s for _, s, _, _ in ps["spans"]] == sorted(
        s for _, s, _, _ in ps["spans"])
    # device 0's ops merged and cut at the window's close
    assert ps["busy"] == {0: [(0, 3 * S), (6 * S, 7 * S),
                              (9.5 * S, 10 * S)], 1: [(0, 1 * S)]}
    assert program_spans.busy_within(ps["busy"][0], 2 * S, 6.5 * S) == \
        pytest.approx(1.5 * S)
    # no window marks, no reading
    assert program_spans.window({}, [sp for sp in _synthetic()[1]
                                     if sp[0] != "bench.open"]) is None


@pytest.mark.parametrize("metric,want", [
    # tick 1: 4.0 s less waits 1.1 + 2.1; tick 2: 3.2 s less 0.15 + 2.5
    ("tick_host_ms_p50", 1e3 * (0.8 + 0.55) / 2),
    # decode [2.0,4.4) and [6.0,8.7): device 0 busy 1 s in each, device
    # 1 none
    ("decode_step_device_ms_p50", 1e3 * 0.5),
    # prefill [0.6,2.0): device 0 busy 1.4 s, device 1 0.4 s; prefill
    # [5.7,6.0): neither
    ("prefill_step_device_ms_p50", 1e3 * (0.9 + 0.0) / 2),
    ("prefill_row_util_pct", 100.0 * (16 + 48) / (64 + 64)),
])
def test_program_span_metrics_hand_count(metric, want):
    device_ops, host = _synthetic()
    mod = harness.plugin("metrics", metric)
    assert mod.reading(program_spans.window(device_ops, host)) == \
        pytest.approx(want)
    # a program without the spans reads nothing, and raises nothing
    bare = [sp for sp in host if not sp[0].startswith("serve.")]
    assert mod.reading(program_spans.window(device_ops, bare)) is None
    if "device" in metric:                   # no device in the trace
        assert mod.reading(program_spans.window({}, host)) is None


def test_tiny_traced_cell_reads_the_program_spans(monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR",
                        ROOT / ".bench_trace" / "program_spans")
    res = test_harness.run_tiny(test_harness.tiny_cell(), trace=True,
                                impl="interpret")
    assert res["correct"], res["compared"]
    m = {k: v["value"] for k, v in res["metrics"].items()}
    # the engine's spans: host time under the whole tick's, and prompt
    # rows a share of the prefill rows computed; no device on the CPU
    assert 0 < m["tick_host_ms_p50"] <= m["tick_ms_p50"]
    assert 0 < m["prefill_row_util_pct"] <= 100
    assert "decode_step_device_ms_p50" not in m
