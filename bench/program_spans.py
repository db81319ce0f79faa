"""The serving program's own spans in a traced run's window, for the
per-layer metrics that read them.

The engine opens host spans named ``serve.*`` with integer args
(``src/repro/serving/spans.py``). They land in the run's profiler trace
on the device's clock. ``read`` takes them from the trace the run left
under ``harness.TRACE_DIR``, with each device's busy intervals, both cut
to the window that the benchmark's ``bench.open`` and ``bench.close``
spans mark. It parses the trace once per run. A program that opens no
such spans gives none, and the metrics that read them read nothing.
"""
from __future__ import annotations

import bisect
import glob
import os

import xplane

PREFIX = "serve."
_cache: dict = {}


def read(rec) -> dict | None:
    """{"spans": [(name, start, end, args)] in time order, "busy": {device:
    merged busy intervals}} of the traced run, in ns on the trace's
    clock; None without a trace."""
    from harness import TRACE_DIR
    files = sorted(glob.glob(os.path.join(str(TRACE_DIR), "plugins",
                                          "profile", "*", "*.xplane.pb")))
    if not files:
        return None
    st = os.stat(files[-1])
    key = (files[-1], st.st_mtime_ns, st.st_size)
    if key not in _cache:
        _cache.clear()
        _cache[key] = window(*_load(files[-1]))
    return _cache[key]


def _load(path: str):
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    device_ops: dict[int, list] = {}
    host = []
    for plane in pd.planes:
        m = xplane.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == xplane.OPS_LINE:
                device_ops.setdefault(int(m.group(1)), []).extend(
                    (ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events)
            elif not m:
                host.extend((ev.name, ev.start_ns,
                             ev.start_ns + ev.duration_ns, xplane._stats(ev))
                            for ev in line.events
                            if ev.name.startswith((PREFIX, "bench.")))
    return device_ops, host


def window(device_ops: dict[int, list[tuple[float, float]]],
           host: list[tuple]) -> dict | None:
    """The ``serve.*`` spans wholly inside the window and each device's
    busy intervals inside it. ``device_ops``: device -> [(start, end)];
    ``host``: (name, start, end, args) host events, the window's
    ``bench.open`` and ``bench.close`` among them."""
    opens = [s for n, s, *_ in host if n == "bench.open"]
    closes = [s for n, s, *_ in host if n == "bench.close"]
    if not opens or not closes:
        return None
    lo, hi = min(opens), max(closes)
    spans = sorted((sp for sp in host if sp[0].startswith(PREFIX)
                    and lo <= sp[1] and sp[2] <= hi), key=lambda sp: sp[1])
    busy = {dev: xplane.union(xplane.clip(ops, lo, hi))
            for dev, ops in sorted(device_ops.items())}
    return {"spans": spans, "busy": busy}


def busy_within(merged: list[tuple[float, float]], lo: float,
                hi: float) -> float:
    """Nanoseconds of the merged busy intervals inside [lo, hi)."""
    i = max(0, bisect.bisect_right(merged, (lo,)) - 1)
    total = 0.0
    while i < len(merged) and merged[i][0] < hi:
        total += max(0.0, min(merged[i][1], hi) - max(merged[i][0], lo))
        i += 1
    return total


def device_ms(ps: dict, name: str) -> list[float]:
    """Per span called ``name``: the milliseconds inside it in which an
    operation ran on the device, averaged over the devices; empty where
    the trace holds no device."""
    busy = ps["busy"]
    if not busy:
        return []
    return [sum(busy_within(m, s, e) for m in busy.values())
            / len(busy) / 1e6 for n, s, e, _ in ps["spans"] if n == name]
