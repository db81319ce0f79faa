"""Jamba (AI21-Jamba2-Mini's block): its plain reference forward pass, its
kernel calls and FLOPs, and the adapter that lays the seeded weights out
as the program's parameter tree.

Layer equations (``JambaForCausalLM`` as published): pre-RMSNorm blocks,
each a mixer then a feed-forward, both residual.

- Layer i is attention where ``i % attn_layer_period ==
  attn_layer_offset``, else a Mamba-1 mixer; its feed-forward is the
  sparse MoE where ``i % expert_layer_period == expert_layer_offset``,
  else a SwiGLU MLP.
- Attention: GQA, no biases, no positional encoding, causal softmax with
  scale 1/sqrt(head_dim).
- Mamba: in_proj to (x, z); causal depthwise conv of ``mamba_d_conv``
  taps with bias; silu; x_proj to (dt, B, C), each through its RMSNorm;
  dt = softplus(dt_proj(dt) + dt_bias); the selective scan h_t =
  exp(dt A) h_{t-1} + dt B_t x_t, y_t = C_t h_t + D x_t, with A =
  -exp(A_log), run one position after another; out_proj(y * silu(z)).
- MoE: softmax over every expert's router logit, the top
  ``num_experts_per_tok``, their probabilities not renormalized; every
  token through each of its experts (SwiGLU), weighted, with no
  capacity.
- Final RMSNorm and an untied linear head.

The reference runs in float32 at the highest matmul precision, layer by
layer, regenerating each layer's weights (each expert's, one at a time)
from the seed and quantizing them with ``spx_ref``; it imports nothing of
the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import seeded_weights as sw
import spx_ref

#: dt_proj's bias is seeded around this value, softplus(-4.6) ~ 0.01
DT_BIAS = -4.6

# -- shapes -----------------------------------------------------------------


def layer_kinds(c: dict) -> list[tuple[str, str]]:
    """(mixer, feed-forward) of each layer: ("attn" | "mamba", "moe" |
    "dense")."""
    return [("attn" if i % c["attn_layer_period"] == c["attn_layer_offset"]
             else "mamba",
             "moe" if i % c["expert_layer_period"]
             == c["expert_layer_offset"] else "dense")
            for i in range(c["num_hidden_layers"])]


def dims(c: dict) -> dict:
    """Sizes; ``layers`` counts the attention layers (the paged KV)."""
    d, hq, hkv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    return {"d": d, "hq": hq, "hkv": hkv, "dh": d // hq,
            "f": c["intermediate_size"], "v": c["vocab_size"],
            "e": c["num_experts"], "k": c["num_experts_per_tok"],
            "di": c["mamba_expand"] * d, "ds": c["mamba_d_state"],
            "dc": c["mamba_d_conv"], "dtr": c["mamba_dt_rank"],
            "layers": sum(m == "attn" for m, _ in layer_kinds(c))}


def mixer_leaves(c: dict, mixer: str) -> dict:
    """name -> (shape, kind) of one layer's mixer weights."""
    m = dims(c)
    d, di, ds, dtr = m["d"], m["di"], m["ds"], m["dtr"]
    if mixer == "attn":
        q, kv = m["hq"] * m["dh"], m["hkv"] * m["dh"]
        return {"input_layernorm": ((d,), "gain"),
                "q_proj": ((d, q), "matrix"), "k_proj": ((d, kv), "matrix"),
                "v_proj": ((d, kv), "matrix"), "o_proj": ((q, d), "matrix")}
    return {"input_layernorm": ((d,), "gain"),
            "in_proj": ((d, 2 * di), "matrix"),
            "conv_w": ((m["dc"], di), "matrix"), "conv_b": ((di,), "bias"),
            "x_proj": ((di, dtr + 2 * ds), "matrix"),
            "dt_layernorm": ((dtr,), "gain"),
            "b_layernorm": ((ds,), "gain"), "c_layernorm": ((ds,), "gain"),
            "dt_proj": ((dtr, di), "matrix"), "dt_bias": ((di,), "bias"),
            "A_log": ((di, ds), "bias"), "D": ((di,), "gain"),
            "out_proj": ((di, d), "matrix")}


def ffn_leaves(c: dict, ffn: str) -> dict:
    """name -> (shape, kind) of one layer's feed-forward weights, the
    experts apart (``expert_leaves``)."""
    m = dims(c)
    d, f = m["d"], m["f"]
    if ffn == "moe":
        return {"pre_ff_layernorm": ((d,), "gain"),
                "router": ((d, m["e"]), "matrix")}
    return {"pre_ff_layernorm": ((d,), "gain"),
            "gate_proj": ((d, f), "matrix"), "up_proj": ((d, f), "matrix"),
            "down_proj": ((f, d), "matrix")}


def expert_leaves(c: dict) -> dict:
    """name -> (shape, kind) of one expert; expert e of layer i is drawn
    at index ``i * num_experts + e``."""
    m = dims(c)
    d, f = m["d"], m["f"]
    return {"expert_gate_proj": ((d, f), "matrix"),
            "expert_up_proj": ((d, f), "matrix"),
            "expert_down_proj": ((f, d), "matrix")}


#: leaves the program stores as SPx codes (the rest stay f32)
QUANTIZED = {"in_proj", "x_proj", "dt_proj", "out_proj", "q_proj", "k_proj",
             "v_proj", "o_proj", "gate_proj", "up_proj", "down_proj",
             "expert_gate_proj", "expert_up_proj", "expert_down_proj"}


def top_leaves(c: dict) -> dict:
    m = dims(c)
    return {"embed_tokens": ((m["v"], m["d"]), "embed"),
            "final_layernorm": ((m["d"],), "gain"),
            "lm_head": ((m["d"], m["v"]), "matrix")}


def matmuls(c: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of every quantized projection outside the experts,
    once for each layer it occurs in (``dims()["layers"]`` is 1 in the
    kernel calls' count of them). The experts' work is
    ``bench/work/spx_expert_matmul.py``'s."""
    out = []
    for i, (mixer, ffn) in enumerate(layer_kinds(c)):
        leaves = {**mixer_leaves(c, mixer), **ffn_leaves(c, ffn)}
        out += [(f"{i}.{n}", *shape) for n, (shape, _) in leaves.items()
                if n in QUANTIZED]
    return out


def expert_matmuls(c: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of one expert's projections, for each MoE layer."""
    return [(f"{i}.{n}", *shape)
            for i, (_, ffn) in enumerate(layer_kinds(c)) if ffn == "moe"
            for n, (shape, _) in expert_leaves(c).items()]


# -- FLOPs and kernel calls -------------------------------------------------


def token_flops(c: dict) -> float:
    """Matmul FLOPs of one token through every layer (the head apart):
    the projections, the router, and its ``num_experts_per_tok`` experts
    in each MoE layer."""
    m = dims(c)
    n_moe = sum(f == "moe" for _, f in layer_kinds(c))
    return 2.0 * (sum(k * n for _, k, n in matmuls(c))
                  + m["k"] * sum(k * n for _, k, n in expert_matmuls(c))
                  + n_moe * m["d"] * m["e"])


def head_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def attention_flops(c: dict, attended: float) -> float:
    """QK^T and PV FLOPs over ``attended`` (query, key) pairs, all
    attention layers."""
    m = dims(c)
    return 4.0 * m["layers"] * m["hq"] * m["dh"] * attended


# -- the program's parameter tree -------------------------------------------

#: reference leaf name -> path in the program's per-layer slot tree
PROGRAM_LAYER_PATHS = {
    "input_layernorm": ("norm1", "g"),
    "q_proj": ("attn", "wq", "w"), "k_proj": ("attn", "wk", "w"),
    "v_proj": ("attn", "wv", "w"), "o_proj": ("attn", "wo", "w"),
    "in_proj": ("mamba", "in_proj", "w"), "conv_w": ("mamba", "conv_w"),
    "conv_b": ("mamba", "conv_b"), "x_proj": ("mamba", "x_proj", "w"),
    "dt_layernorm": ("mamba", "dt_norm", "g"),
    "b_layernorm": ("mamba", "b_norm", "g"),
    "c_layernorm": ("mamba", "c_norm", "g"),
    "dt_proj": ("mamba", "dt_proj", "w"), "dt_bias": ("mamba", "dt_proj", "b"),
    "A_log": ("mamba", "A_log"), "D": ("mamba", "D"),
    "out_proj": ("mamba", "out_proj", "w"),
    "pre_ff_layernorm": ("norm2", "g"),
    "gate_proj": ("mlp", "gate", "w"), "up_proj": ("mlp", "up", "w"),
    "down_proj": ("mlp", "down", "w"),
    "router": ("moe", "router", "w"),
}
#: expert leaf name -> key of its (E, K, N) stack under the slot's "moe"
PROGRAM_EXPERT_KEYS = {"expert_gate_proj": "gate", "expert_up_proj": "up",
                       "expert_down_proj": "down"}
PROGRAM_TOP_PATHS = {"embed_tokens": ("embed", "table"),
                     "final_layernorm": ("final_norm", "g"),
                     "lm_head": ("head", "w")}


def _put(tree: dict, path: tuple, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def leaf(key, name: str, layer, shape: tuple, kind: str):
    """A seeded leaf as the model uses it: A_log about log(1..d_state)
    (the published initialization) and dt_bias about ``DT_BIAS``."""
    w = sw.leaf(key, name, layer, shape, kind)
    if name == "A_log":
        w = w + jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32))
    elif name == "dt_bias":
        w = w + DT_BIAS
    return w


def program_params(c: dict, seed: int, quantize):
    """The seeded weights in the program's tree, each matrix passed
    through ``quantize`` (the program's own quantization step) as it is
    made: a jitted draw and a jitted quantization per leaf (the latter
    compiled once per shape), and one jitted ``lax.map`` over the experts
    per expert stack, so one matrix's dense weights are live at a time."""
    return _program_params(c, quantize, sw.seed_key(seed))


def program_params_fn(c: dict, quantize):
    """The whole of ``program_params`` as one jitted function of a seed
    key (for a compile of the weight maker without running it)."""
    return jax.jit(functools.partial(_program_params, c, quantize))


def _program_params(c: dict, quantize, key):
    e = dims(c)["e"]
    draw = jax.jit(leaf, static_argnums=(1, 3, 4))
    quantize_one = jax.jit(quantize)

    def make(key, name, layer, shape, kind):
        w = draw(key, name, layer, shape, kind)
        return quantize_one(w) if name in QUANTIZED else w

    @functools.partial(jax.jit, static_argnums=(1, 3, 4))
    def make_experts(key, name, layer, shape, kind):
        return jax.lax.map(
            lambda j: quantize(leaf(key, name, layer * e + j, shape, kind)),
            jnp.arange(e))

    out: dict = {}
    for name, (shape, kind) in top_leaves(c).items():
        _put(out, PROGRAM_TOP_PATHS[name], make(key, name, None, shape, kind))
    slots = []
    for i, (mixer, ffn) in enumerate(layer_kinds(c)):
        t: dict = {}
        layer = jnp.int32(i)
        for name, (shape, kind) in {**mixer_leaves(c, mixer),
                                    **ffn_leaves(c, ffn)}.items():
            _put(t, PROGRAM_LAYER_PATHS[name],
                 make(key, name, layer, shape, kind))
        if ffn == "moe":
            for name, (shape, kind) in expert_leaves(c).items():
                t["moe"][PROGRAM_EXPERT_KEYS[name]] = make_experts(
                    key, name, layer, shape, kind)
        # the program stacks each slot over periods: one period here
        slots.append(jax.tree_util.tree_map(lambda a: a[None], t))
    out["stack"] = {"slots": slots}
    return out


# -- the reference ----------------------------------------------------------

HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b, precision: str):
    """a @ b in ``precision``: f32 at the highest precision, or with both
    operands rounded to bfloat16 / float8 (e4m3) and f32 accumulation."""
    if precision == "f32":
        return jnp.matmul(a, b, precision=HIGHEST)
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float8_e4m3fn
    a = a.astype(dt).astype(jnp.bfloat16)
    b = b.astype(dt).astype(jnp.bfloat16)
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


@functools.partial(jax.jit, static_argnums=(1,))
def _qdq(w, scheme: str):
    return spx_ref.qdq_weight(w, scheme)


def _weights(key, layer: int, leaves: dict, wq) -> dict:
    """One layer's (or expert's) seeded weights, the quantized ones
    through ``spx_ref``: made one at a time outside the layer programs,
    so each quantization compiles once per shape."""
    return {name: (_qdq(w, wq) if wq and name in QUANTIZED else w)
            for name, (shape, kind) in leaves.items()
            for w in [leaf(key, name, layer, shape, kind)]}


@functools.partial(jax.jit, static_argnames=("cj", "precision"))
def _attention(h, w, *, cj, precision):
    """h + attention(norm(h)) over (n, S, D) hidden states."""
    c = dict(cj)
    m = dims(c)
    hq, hkv, dh = m["hq"], m["hkv"], m["dh"]
    n, s, d = h.shape
    pos = jnp.arange(s)
    mm = functools.partial(_mm, precision=precision)

    def attend(x):                                    # x: (S, D)
        q = mm(x, w["q_proj"]).reshape(s, hq, dh)
        k = jnp.repeat(mm(x, w["k_proj"]).reshape(s, hkv, dh), hq // hkv, 1)
        v = jnp.repeat(mm(x, w["v_proj"]).reshape(s, hkv, dh), hq // hkv, 1)
        sc = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) * dh ** -0.5
        sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = mm(p, v.transpose(1, 0, 2)).transpose(1, 0, 2)
        return mm(o.reshape(s, hq * dh), w["o_proj"])

    x = _rms(h, w["input_layernorm"], c["rms_norm_eps"])
    return h + jax.lax.map(attend, x)


@functools.partial(jax.jit, static_argnames=("cj", "precision"))
def _mamba(h, w, *, cj, precision):
    """h + mamba(norm(h)) over (n, S, D) hidden states."""
    c = dict(cj)
    m = dims(c)
    di, ds, dc, dtr = m["di"], m["ds"], m["dc"], m["dtr"]
    eps = c["rms_norm_eps"]
    n, s, _ = h.shape
    mm = functools.partial(_mm, precision=precision)
    xz = mm(_rms(h, w["input_layernorm"], eps), w["in_proj"])
    x, z = xz[..., :di], xz[..., di:]
    xp = jnp.pad(x, ((0, 0), (dc - 1, 0), (0, 0)))
    x = sum(xp[:, j:j + s] * w["conv_w"][j] for j in range(dc)) + w["conv_b"]
    x = jax.nn.silu(x)
    p = mm(x, w["x_proj"])
    dt = _rms(p[..., :dtr], w["dt_layernorm"], eps)
    b = _rms(p[..., dtr:dtr + ds], w["b_layernorm"], eps)
    cc = _rms(p[..., dtr + ds:], w["c_layernorm"], eps)
    dt = jax.nn.softplus(mm(dt, w["dt_proj"]) + w["dt_bias"])
    a = -jnp.exp(w["A_log"])

    def step(state, t):
        state = jnp.exp(dt[:, t, :, None] * a) * state \
            + (dt[:, t] * x[:, t])[..., None] * b[:, t, None, :]
        return state, jnp.einsum("nds,ns->nd", state, cc[:, t],
                                 precision=HIGHEST)

    _, y = jax.lax.scan(step, jnp.zeros((n, di, ds), jnp.float32),
                        jnp.arange(s))
    y = y.transpose(1, 0, 2) + x * w["D"]
    return h + mm(y * jax.nn.silu(z), w["out_proj"])


@functools.partial(jax.jit, static_argnames=("cj", "precision"))
def _dense_ffn(h, w, *, cj, precision):
    c = dict(cj)
    mm = functools.partial(_mm, precision=precision)
    x = _rms(h, w["pre_ff_layernorm"], c["rms_norm_eps"])
    return h + mm(jax.nn.silu(mm(x, w["gate_proj"])) * mm(x, w["up_proj"]),
                  w["down_proj"])


@functools.partial(jax.jit, static_argnames=("cj",))
def _route(h, w, *, cj):
    """(normed input, each token's weight for every expert: its softmax
    probability where the expert is among its top k, else 0)."""
    c = dict(cj)
    x = _rms(h, w["pre_ff_layernorm"], c["rms_norm_eps"])
    probs = jax.nn.softmax(jnp.matmul(x, w["router"], precision=HIGHEST), -1)
    kth = jax.lax.top_k(probs, c["num_experts_per_tok"])[0][..., -1:]
    return x, jnp.where(probs >= kth, probs, 0.0)


@functools.partial(jax.jit, static_argnames=("precision",))
def _expert(h, x, weight, w, *, precision):
    """h + weight * expert(x), every token through one expert; a token
    that does not route to it has weight 0."""
    mm = functools.partial(_mm, precision=precision)
    y = mm(jax.nn.silu(mm(x, w["expert_gate_proj"]))
           * mm(x, w["expert_up_proj"]), w["expert_down_proj"])
    return h + weight[..., None] * y


@functools.partial(jax.jit, static_argnames=("cj", "precision"))
def _head(h_rows, key, *, cj, precision):
    c = dict(cj)
    tops = top_leaves(c)
    g = leaf(key, "final_layernorm", None, *tops["final_layernorm"])
    head = leaf(key, "lm_head", None, *tops["lm_head"])
    return _mm(_rms(h_rows, g, c["rms_norm_eps"]), head, precision)


@functools.partial(jax.jit, static_argnames=("cj",))
def _embed(tokens, key, *, cj):
    c = dict(cj)
    table = leaf(key, "embed_tokens", None, *top_leaves(c)["embed_tokens"])
    return table[tokens]


def _frozen(c: dict):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, bool, str))))


def reference_logits(c: dict, seed: int, seqs: list[np.ndarray],
                     rows: list[tuple[int, int]], *, weight_scheme: str,
                     kv_scheme: str | None, precision: str = "f32",
                     length: int | None = None):
    """Logits (len(rows), V) of the plain forward pass over ``seqs`` (token
    arrays; padded to ``length`` or the longest, causal so padding never
    reaches an earlier position), at each (sequence, position) of
    ``rows``. The KV cache is f32 in every Jamba configuration, so
    ``kv_scheme`` must be None."""
    if kv_scheme is not None:
        raise ValueError("the jamba reference has no quantized KV")
    cj = _frozen(c)
    key = sw.seed_key(seed)
    e = dims(c)["e"]
    s = length or max(len(q) for q in seqs)
    toks = np.zeros((len(seqs), s), np.int32)
    for i, q in enumerate(seqs):
        toks[i, :len(q)] = q
    wq = weight_scheme
    kw = dict(cj=cj, precision=precision)
    with jax.default_matmul_precision("highest"):
        h = _embed(jnp.asarray(toks), key, cj=cj)
        for i, (mixer, ffn) in enumerate(layer_kinds(c)):
            w = _weights(key, i, mixer_leaves(c, mixer), wq)
            h = (_attention if mixer == "attn" else _mamba)(h, w, **kw)
            w = _weights(key, i, ffn_leaves(c, ffn), wq)
            if ffn == "dense":
                h = _dense_ffn(h, w, **kw)
                continue
            x, weights = _route(h, w, cj=cj)
            for j in range(e):
                h = _expert(h, x, weights[..., j],
                            _weights(key, i * e + j, expert_leaves(c), wq),
                            precision=precision)
        idx = np.asarray(rows, np.int32)
        return _head(h[idx[:, 0], idx[:, 1]], key, cj=cj,
                     precision=precision)
