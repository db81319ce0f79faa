"""Share of its roofline that the paged_decode_ragged_quant kernel
reached in the window: the least time of the calls made (the larger of
FLOPs over the bf16 peak and bytes over HBM bandwidth, from
bench/work/paged_decode_ragged_quant.py) over the kernel's device time
in the trace, in percent."""

KERNEL = "paged_decode_ragged_quant"


def compute(rec):
    from harness import log
    calls = [c for c in rec["work"]["paged_attention"] if c["kv"] != "f32"]
    share, bound = rec["roofline"](KERNEL, calls, rec["trace"], rec["peak"])
    if share is not None:
        log(f"{KERNEL}_roofline: {share:.3f}% of the {bound} bound")
    return share
