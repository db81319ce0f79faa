"""Share of its roofline that the spx_matmul kernel reached in the
window: the least time of the calls made (the larger of FLOPs over the
bf16 peak and bytes over HBM bandwidth, from bench/work/spx_matmul.py)
over the kernel's device time in the trace, in percent."""

KERNEL = "spx_matmul"


def compute(rec):
    from harness import log
    calls = rec["work"]["spx_matmul"]
    share, bound = rec["roofline"](KERNEL, calls, rec["trace"], rec["peak"])
    if share is not None:
        log(f"{KERNEL}_roofline: {share:.3f}% of the {bound} bound")
    return share
