"""Work of one spx_matmul call: x (M, K) f32 @ SPx-coded W (K, N), per
layer, over ``layers`` layers. M counts the rows that carry a token (a
decode row or a prompt token), not padding, so a kernel that skips
padding is read against the same work."""

#: trace event names (or their HLO long names) of this kernel
MATCH = [r"spx_matmul"]


def work(call: dict) -> tuple[float, float]:
    m, k, n, layers = call["m"], call["k"], call["n"], call["layers"]
    flops = 2.0 * m * k * n
    nbytes = k * n * call["bits"] / 8 + 4.0 * n + 4.0 * m * k + 4.0 * m * n
    return layers * flops, layers * nbytes
