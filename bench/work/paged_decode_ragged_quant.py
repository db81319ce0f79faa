"""Work of one decode tick's paged attention over SPx-coded KV pages,
per layer, over ``layers`` layers: each attended token's K and V as one
uint8 code per element plus one f32 scale per token and head, and one
output per query head."""

MATCH = [r"paged_decode_ragged_quant"]


def work(call: dict) -> tuple[float, float]:
    hq, hkv, dh, layers = call["hq"], call["hkv"], call["dh"], call["layers"]
    attended = sum(call["attended"])
    rows = len(call["attended"])
    flops = 4.0 * hq * dh * attended
    nbytes = 2 * attended * hkv * (dh + 4.0) + 2 * rows * hq * dh * 4.0
    return layers * flops, layers * nbytes
