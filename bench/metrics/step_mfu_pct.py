"""Model FLOPs of the useful work in the window (every prompt and decode
token through the layer matmuls, attention over the context each
attends, the head for each emitted token) over the time the window's
engine steps took x chips x the chip's bf16 peak, in percent: the
step's share of the peak, which a faster step raises even where the
offered load fixes the work."""


def compute(rec):
    flops = rec["work"]["flops"]
    step_s = sum(t.t1 - t.t0 for t in rec["rec"]["ticks"])
    if flops <= 0 or step_s <= 0:
        return None
    peak = rec["peak"]["bf16_flops_per_s"] * rec["chips"]
    return 100.0 * flops / (step_s * peak)
