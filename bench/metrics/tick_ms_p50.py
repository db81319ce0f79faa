"""Median host time of one engine (or router) step() in the window; the
step returns once the tick's logits are on the host."""
import statistics


def compute(rec):
    ticks = rec["rec"]["ticks"]
    if not ticks:
        return None
    return 1e3 * statistics.median(t.t1 - t.t0 for t in ticks)
