"""Share of its roofline that the spx_expert_matmul kernel reached in the
window: the least time of the calls made (the larger of FLOPs over the
bf16 peak and bytes over HBM bandwidth, from
bench/work/spx_expert_matmul.py) over the kernel's device time in the
trace, in percent. The calls are built from the rows the harness counts
for the dense projections: each launch of the window (a tick's prefill
rows or its decode rows) sends rows x ``num_experts_per_tok`` pairs
through each expert matrix of every MoE layer. None for a model without
experts."""

KERNEL = "spx_expert_matmul"


def calls(rec) -> list[dict]:
    from harness import model_module, weight_bits
    c = rec["config"]
    mm = model_module(c)
    if not hasattr(mm, "expert_matmuls"):
        return []
    d = mm.dims(c)
    # the harness lists one call per projection of ``matmuls`` for each
    # launch, in that order: every len(matmuls)-th is one launch
    rows = [x["m"] for x in rec["work"]["spx_matmul"][::len(mm.matmuls(c))]]
    return [{"pairs": m * d["k"], "k": k, "n": n, "bits": weight_bits(c),
             "experts": d["e"], "layers": 1}
            for m in rows for _, k, n in mm.expert_matmuls(c)]


def compute(rec):
    from harness import log
    share, bound = rec["roofline"](KERNEL, calls(rec), rec["trace"],
                                   rec["peak"])
    if share is not None:
        log(f"{KERNEL}_roofline: {share:.3f}% of the {bound} bound")
    return share
