"""Qwen2 decoder, text path (the language model of Qwen2-VL): its plain
reference forward pass, its kernel calls and FLOPs, and the adapter that
lays the seeded weights out as the program's parameter tree.

Layer equations (Qwen2 / Qwen2-VL as published): pre-RMSNorm blocks,
GQA attention with q/k/v biases and rotary positions (rotate-half; for
text the three M-RoPE position streams are all the token index, which is
plain RoPE), causal softmax with scale 1/sqrt(head_dim), SwiGLU MLP
(down(silu(gate(x)) * up(x))), final RMSNorm and a linear head.

The reference runs in float32 at the highest matmul precision, layer by
layer, regenerating each layer's weights from the seed and quantizing
them with ``spx_ref``; it imports nothing of the program.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

import seeded_weights as sw
import spx_ref

# -- shapes -----------------------------------------------------------------


def dims(c: dict) -> dict:
    d, hq, hkv = c["hidden_size"], c["num_attention_heads"], \
        c["num_key_value_heads"]
    return {"d": d, "hq": hq, "hkv": hkv, "dh": d // hq,
            "f": c["intermediate_size"], "v": c["vocab_size"],
            "layers": c["num_hidden_layers"]}


def layer_leaves(c: dict) -> dict:
    """name -> (shape, kind) of one layer's weights."""
    m = dims(c)
    d, q, kv, f = m["d"], m["hq"] * m["dh"], m["hkv"] * m["dh"], m["f"]
    return {"input_layernorm": ((d,), "gain"),
            "q_proj": ((d, q), "matrix"), "q_bias": ((q,), "bias"),
            "k_proj": ((d, kv), "matrix"), "k_bias": ((kv,), "bias"),
            "v_proj": ((d, kv), "matrix"), "v_bias": ((kv,), "bias"),
            "o_proj": ((q, d), "matrix"),
            "post_attention_layernorm": ((d,), "gain"),
            "gate_proj": ((d, f), "matrix"), "up_proj": ((d, f), "matrix"),
            "down_proj": ((f, d), "matrix")}


def matmuls(c: dict) -> list[tuple[str, int, int]]:
    """(name, K, N) of every quantized projection of one layer."""
    return [(n, *shape) for n, (shape, kind) in layer_leaves(c).items()
            if kind == "matrix"]


def top_leaves(c: dict) -> dict:
    m = dims(c)
    out = {"embed_tokens": ((m["v"], m["d"]), "embed"),
           "norm": ((m["d"],), "gain")}
    if not c["tie_word_embeddings"]:
        out["lm_head"] = ((m["d"], m["v"]), "matrix")
    return out


# -- FLOPs and kernel calls -------------------------------------------------


def token_flops(c: dict) -> float:
    """Matmul FLOPs of one token through every layer (the head apart)."""
    return 2.0 * c["num_hidden_layers"] * sum(k * n for _, k, n in matmuls(c))


def head_flops(c: dict) -> float:
    return 2.0 * c["hidden_size"] * c["vocab_size"]


def attention_flops(c: dict, attended: float) -> float:
    """QK^T and PV FLOPs over ``attended`` (query, key) pairs, all layers."""
    m = dims(c)
    return 4.0 * m["layers"] * m["hq"] * m["dh"] * attended


# -- the program's parameter tree -------------------------------------------

#: reference leaf name -> path in the program's per-layer slot tree
PROGRAM_LAYER_PATHS = {
    "input_layernorm": ("norm1", "g"),
    "q_proj": ("attn", "wq", "w"), "q_bias": ("attn", "wq", "b"),
    "k_proj": ("attn", "wk", "w"), "k_bias": ("attn", "wk", "b"),
    "v_proj": ("attn", "wv", "w"), "v_bias": ("attn", "wv", "b"),
    "o_proj": ("attn", "wo", "w"),
    "post_attention_layernorm": ("norm2", "g"),
    "gate_proj": ("mlp", "gate", "w"), "up_proj": ("mlp", "up", "w"),
    "down_proj": ("mlp", "down", "w"),
}
PROGRAM_TOP_PATHS = {"embed_tokens": ("embed", "table"),
                     "norm": ("final_norm", "g"), "lm_head": ("head", "w")}


def _put(tree: dict, path: tuple, value):
    for k in path[:-1]:
        tree = tree.setdefault(k, {})
    tree[path[-1]] = value


def program_params(c: dict, seed: int, quantize):
    """The seeded weights in the program's tree, each layer's matrices
    passed through ``quantize`` (the program's own quantization step) as
    they are made: one jitted call, one layer's dense weights live at a
    time."""
    return program_params_fn(c, quantize)(sw.seed_key(seed))


def program_params_fn(c: dict, quantize):
    """The jitted function of a seed key behind ``program_params``."""
    leaves, tops = layer_leaves(c), top_leaves(c)

    def one_layer(key, layer):
        t: dict = {}
        for name, (shape, kind) in leaves.items():
            _put(t, PROGRAM_LAYER_PATHS[name],
                 sw.leaf(key, name, layer, shape, kind))
        return quantize(t)

    @jax.jit
    def make(key):
        out: dict = {}
        for name, (shape, kind) in tops.items():
            _put(out, PROGRAM_TOP_PATHS[name],
                 sw.leaf(key, name, None, shape, kind))
        stacked = jax.lax.map(lambda l: one_layer(key, l),
                              jnp.arange(c["num_hidden_layers"]))
        out["stack"] = {"slots": [stacked]}
        return out

    return make


# -- the reference ----------------------------------------------------------

def _mm(a, b, precision: str):
    """a @ b in ``precision``: f32 at the highest precision, or with both
    operands rounded to bfloat16 / float8 (e4m3) and f32 accumulation."""
    if precision == "f32":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float8_e4m3fn
    a = a.astype(dt).astype(jnp.bfloat16)
    b = b.astype(dt).astype(jnp.bfloat16)
    return jnp.matmul(a, b, preferred_element_type=jnp.float32)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    """x: (S, H, dh), rotate-half RoPE at integer positions ``pos`` (S,)."""
    dh = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, dh, 2, dtype=np.float64) / dh)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("cj", "precision", "wq", "kvq"))
def _layer(h, key, layer, *, cj, precision, wq, kvq):
    """One decoder layer over (n, S, D) hidden states."""
    c = dict(cj)
    m = dims(c)
    hq, hkv, dh = m["hq"], m["hkv"], m["dh"]
    w = {name: sw.leaf(key, name, layer, shape, kind)
         for name, (shape, kind) in layer_leaves(c).items()}
    for name, kind in ((n, k) for n, (_, k) in layer_leaves(c).items()):
        if kind == "matrix" and wq:
            w[name] = spx_ref.qdq_weight(w[name], wq)
    eps = c["rms_norm_eps"]
    n, s, d = h.shape
    pos = jnp.arange(s)
    mm = functools.partial(_mm, precision=precision)

    def attend(x):                                    # x: (S, D)
        q = (mm(x, w["q_proj"]) + w["q_bias"]).reshape(s, hq, dh)
        k = (mm(x, w["k_proj"]) + w["k_bias"]).reshape(s, hkv, dh)
        v = (mm(x, w["v_proj"]) + w["v_bias"]).reshape(s, hkv, dh)
        q, k = _rope(q, pos, c["rope_theta"]), _rope(k, pos, c["rope_theta"])
        if kvq:
            k, v = spx_ref.qdq_rows(k, kvq), spx_ref.qdq_rows(v, kvq)
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
        sc = mm(q.transpose(1, 0, 2), k.transpose(1, 2, 0)) * dh ** -0.5
        sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = mm(p, v.transpose(1, 0, 2)).transpose(1, 0, 2)
        return mm(o.reshape(s, hq * dh), w["o_proj"])

    x = _rms(h, w["input_layernorm"], eps)
    h = h + jax.lax.map(attend, x)
    x = _rms(h, w["post_attention_layernorm"], eps)
    act = jax.nn.silu(mm(x, w["gate_proj"])) * mm(x, w["up_proj"])
    return h + mm(act, w["down_proj"])


@functools.partial(jax.jit, static_argnames=("cj", "precision"))
def _head(h_rows, key, *, cj, precision):
    c = dict(cj)
    tops = top_leaves(c)
    g = sw.leaf(key, "norm", None, *tops["norm"])
    if c["tie_word_embeddings"]:
        head = sw.leaf(key, "embed_tokens", None, *tops["embed_tokens"]).T
    else:
        head = sw.leaf(key, "lm_head", None, *tops["lm_head"])
    return _mm(_rms(h_rows, g, c["rms_norm_eps"]), head, precision)


@functools.partial(jax.jit, static_argnames=("cj",))
def _embed(tokens, key, *, cj):
    c = dict(cj)
    table = sw.leaf(key, "embed_tokens", None, *top_leaves(c)["embed_tokens"])
    return table[tokens]


def _frozen(c: dict):
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, bool, str))))


def reference_logits(c: dict, seed: int, seqs: list[np.ndarray],
                     rows: list[tuple[int, int]], *, weight_scheme: str,
                     kv_scheme: str | None, precision: str = "f32",
                     length: int | None = None):
    """Logits (len(rows), V) of the plain forward pass over ``seqs`` (token
    arrays; padded to ``length`` or the longest, causal so padding is
    never attended), at each (sequence, position) of ``rows``."""
    cj = _frozen(c)
    key = sw.seed_key(seed)
    s = length or max(len(q) for q in seqs)
    toks = np.zeros((len(seqs), s), np.int32)
    for i, q in enumerate(seqs):
        toks[i, :len(q)] = q
    h = _embed(jnp.asarray(toks), key, cj=cj)
    for layer in range(c["num_hidden_layers"]):
        h = _layer(h, key, jnp.int32(layer), cj=cj, precision=precision,
                   wq=weight_scheme, kvq=kv_scheme)
    idx = np.asarray(rows, np.int32)
    rows_h = h[idx[:, 0], idx[:, 1]]
    return _head(rows_h, key, cj=cj, precision=precision)
