"""Work of one decode tick's paged attention over f32 KV pages, per
layer, over ``layers`` layers: every decoding row reads the K and V of
the tokens it attends (its context, not the pages allocated) and writes
one output per query head."""

MATCH = [r"paged_decode_ragged(?!_quant)"]

KV_BYTES = 4.0          # one f32 element


def work(call: dict) -> tuple[float, float]:
    hq, hkv, dh, layers = call["hq"], call["hkv"], call["dh"], call["layers"]
    attended = sum(call["attended"])
    rows = len(call["attended"])
    flops = 4.0 * hq * dh * attended
    nbytes = 2 * attended * hkv * dh * KV_BYTES + 2 * rows * hq * dh * 4.0
    return layers * flops, layers * nbytes
