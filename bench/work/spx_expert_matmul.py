"""Work of one spx_expert_matmul call: the routed pairs' rows (M, K) f32
@ the SPx-coded weight (K, N) of each pair's expert, over ``layers``
layers. M counts the (token, expert) pairs whose row carries a token,
not padding; the bytes are the codes and scales of every expert held
(each expert's weight is streamed once a call) and the activations."""

#: trace event names (or their HLO long names) of this kernel
MATCH = [r"spx_expert_matmul"]


def work(call: dict) -> tuple[float, float]:
    m, k, n, layers = call["pairs"], call["k"], call["n"], call["layers"]
    flops = 2.0 * m * k * n
    nbytes = (call["experts"] * (k * n * call["bits"] / 8 + 4.0 * n)
              + 4.0 * m * k + 4.0 * m * n)
    return layers * flops, layers * nbytes
