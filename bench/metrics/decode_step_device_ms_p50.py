"""Median device time of one decode launch: per ``serve.decode`` span of
the program in the window, the time inside it in which an operation ran
on the device (the busy union, averaged over the cell's chips). The
engine waits for each launch before the next, so that time is the
decode program's. Read from the trace by bench/program_spans.py."""
import statistics

import program_spans


def reading(ps):
    ms = program_spans.device_ms(ps, "serve.decode")
    return statistics.median(ms) if ms else None


def compute(rec):
    ps = program_spans.read(rec)
    return reading(ps) if ps else None
