"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the metrics
read: per device, the union of intervals in which an operation ran; the
device time of each operation name; and the idle gaps, each labelled by
the innermost benchmark span open on the host at its middle.

Device planes are ``/device:TPU:<n>`` (their ``XLA Ops`` line holds one
event per operation); the benchmark's spans are host events whose names
start with ``bench.``. All times are nanoseconds on the trace's clock.
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
#: operations that contain others on the same line (a scan's loop): they
#: count toward busy time but not as operations of their own
CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\s=]")


def op_name(name: str) -> str:
    """``%spx_matmul_pallas.64 = f32[...] custom-call(...)`` ->
    ``spx_matmul_pallas``: the HLO instruction's name without its
    instance number."""
    return re.sub(r"\.\d+$", "", name.split(" = ")[0].lstrip("%").strip())


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged copy of [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The idle intervals of [lo, hi) around the merged ``busy`` ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def innermost(spans: list[tuple[str, float, float]], t: float) -> str:
    """Name of the shortest span containing ``t`` ("none" outside all)."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "none"


def reduce(device_ops: dict[int, list[tuple[str, dict, float, float]]],
           spans: list[tuple[str, float, float]], lo: float, hi: float,
           top: int = 10) -> dict:
    """device_ops: device -> [(name, stats, start, end)]; spans: host
    spans; [lo, hi): the measured window. Returns busy and window
    seconds (busy averaged over devices), per-op device seconds (summed
    over devices), the ops' events for kernel matching, and the longest
    idle gaps by the span open in them."""
    busy_s, op_s = [], collections.Counter()
    idle: list[tuple[float, str]] = []
    events = []
    for dev, ops in sorted(device_ops.items()):
        iv = clip([(s, e) for _, _, s, e in ops], lo, hi)
        merged = union(iv)
        busy_s.append(sum(e - s for s, e in merged) / 1e9)
        for name, stats, s, e in ops:
            c = clip([(s, e)], lo, hi)
            if c and not CONTAINERS.match(name):
                d = (c[0][1] - c[0][0]) / 1e9
                op_s[op_name(name)] += d
                events.append((name, stats, d))
        for s, e in gaps(merged, lo, hi):
            idle.append(((e - s) / 1e9, innermost(spans, (s + e) / 2)))
    by_span = collections.Counter()
    for d, name in idle:
        by_span[name] += d
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_s) / len(busy_s) if busy_s else 0.0,
        "n_devices": len(busy_s),
        "op_s": dict(op_s),
        "events": events,
        "device_ops": [[n, s] for n, s in op_s.most_common(top)],
        "idle_gaps": [[n, s] for n, s in by_span.most_common(top)],
    }


def _stats(ev) -> dict:
    out = {}
    for k, v in ev.stats:
        if isinstance(v, (str, int, float)):
            out[k] = v
    return out


def load(trace_dir: str):
    """(device_ops, spans) from the newest ``*.xplane.pb`` under
    ``trace_dir``."""
    import jax
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(files[-1])
    device_ops: dict[int, list] = {}
    spans = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                device_ops.setdefault(int(m.group(1)), []).extend(
                    (ev.name, _stats(ev), ev.start_ns,
                     ev.start_ns + ev.duration_ns) for ev in line.events)
            elif not m:
                spans.extend((ev.name, ev.start_ns,
                              ev.start_ns + ev.duration_ns)
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return device_ops, spans
