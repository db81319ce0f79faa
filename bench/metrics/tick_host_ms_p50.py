"""Median host time of one engine tick in the window: per ``serve.tick``
span of the program, its duration less the ``serve.*.wait`` spans inside
it, in which the host waits for a launch's logits (the device's work and
the copy to the host). Read from the trace by bench/program_spans.py."""
import statistics

import program_spans


def reading(ps):
    spans = ps["spans"]
    waits = [(s, e) for n, s, e, _ in spans if n.endswith(".wait")]
    host = [e - s - sum(we - ws for ws, we in waits if s <= ws and we <= e)
            for n, s, e, _ in spans if n == "serve.tick"]
    if not host:
        return None
    return statistics.median(host) / 1e6


def compute(rec):
    ps = program_spans.read(rec)
    return reading(ps) if ps else None
