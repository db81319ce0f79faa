"""Jamba (``jamba2-mini``) against its plain reference, on the CPU at a
small size with seeded random weights:

- prefill and cache decode through ``ServeEngine`` (paged, ``cb``, fused
  decode) give the logits of the reference's full forward pass
  (``bench/models/jamba.py``);
- the dropless MoE layer equals the per-token sum over each token's
  experts, under skewed routing and whatever the batch-mates, and on an
  expert-parallel mesh the single device's output;
- the grouped SPx expert kernel (interpret mode) equals its jnp oracle,
  with empty and full tiles;
- the Mamba dt/B/C norms change the output only where the block has them;
- NoPE attention ignores positions.
"""
from __future__ import annotations

import dataclasses
import json
import re
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core.quantized import quantize_weight
from repro.kernels import ops
from repro.models import lm as lm_mod
from repro.nn import moe, ssm
from repro.runtime import Runtime
from repro.serving import ServeConfig, ServeEngine
from repro.serving.engine import Request

jax.config.update("jax_platform_name", "cpu")

BENCH = Path(__file__).resolve().parents[1] / "bench"
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402

RT = Runtime(impl="ref", q_chunk=16)


def small_config() -> dict:
    """The benchmark's ``jamba2-mini`` file at hidden 64 (4 heads of 16,
    2 KV heads), 8 experts of width 128, d_state 8, dt_rank 8 and a
    256-token vocabulary: one whole period, every mechanism kept."""
    c = json.loads((BENCH / "configs" / "jamba2-mini.json").read_text())
    return dict(c, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2,
                vocab_size=256, num_experts=8, mamba_d_state=8,
                mamba_dt_rank=8)


def small_arch(c: dict):
    return dataclasses.replace(
        harness.arch_config(c), n_experts=c["num_experts"],
        ssm_d_state=c["mamba_d_state"], ssm_dt_rank=c["mamba_dt_rank"])


def test_engine_logits_match_the_reference():
    """Every served token's logits, from the prefill that picked the first
    and from each cache decode after it, against the reference forward
    over the prompt and the tokens before it. Weights are f32 here, so
    both sides compute the same f32 products and differ only in the order
    of their sums (chunked prefill against whole-sequence attention, the
    expert tiles against the reference's per-expert pass): a few units of
    f32 rounding over eight layers, far below the 1e-3 allowed on logits
    of size about 1. The SPx weights' own agreement is the benchmark
    check's (bench/test_jamba.py)."""
    c = small_config()
    jm = harness.model_module(c)
    params = jm.program_params(c, 2 ** 40 + 3, lambda t: t)
    eng = ServeEngine(params, small_arch(c),
                      ServeConfig(batch_slots=3, max_seq=64, quantize=None,
                                  kv_layout="paged", scheduler="cb",
                                  fused_decode=True, prefill_chunk=8),
                      rt=RT)
    seen: dict[int, list] = {}
    pick, accept = eng._pick_token, eng._accept_tokens

    def picked(row, req):
        seen.setdefault(req.rid, []).append(np.asarray(row))
        return pick(row, req)

    def accepted(req, drafts, logits):
        seen.setdefault(req.rid, []).append(np.asarray(logits[0]))
        return accept(req, drafts, logits)
    eng._pick_token, eng._accept_tokens = picked, accepted
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, c["vocab_size"], n).astype(np.int32)
               for n in (5, 19, 11, 30)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=6))
    eng.run()
    assert eng.metrics()["launches"]["decode"] > 0
    reqs = sorted(eng.finished, key=lambda r: r.rid)
    seqs = [np.concatenate([r.prompt, np.asarray(r.output[:-1], np.int32)])
            for r in reqs]
    rows = [(i, len(r.prompt) - 1 + j) for i, r in enumerate(reqs)
            for j in range(len(r.output))]
    want = np.asarray(jm.reference_logits(c, 2 ** 40 + 3, seqs, rows,
                                          weight_scheme=None, kv_scheme=None))
    got = np.stack([row for r in reqs for row in seen[r.rid]])
    np.testing.assert_allclose(got, want, atol=1e-3, rtol=0)
    assert [t for r in reqs for t in r.output] == list(want.argmax(-1))


# -- the dropless MoE layer ---------------------------------------------------

def _moe_params(key, d=16, f=32, e=4):
    return moe.moe_init(key, d, f, e)


def _per_token(p, x, top_k, renormalize):
    """Each token through each of its top-k experts, one token at a time."""
    xf = np.asarray(x.reshape(-1, x.shape[-1]), np.float64)
    w = np.asarray(p["router"]["w"], np.float64)
    out = []
    for t in xf:
        logits = t @ w
        pr = np.exp(logits - logits.max())
        pr /= pr.sum()
        top = np.argsort(-pr)[:top_k]
        wts = pr[top] / (pr[top].sum() if renormalize else 1.0)
        y = 0.0
        for e, a in zip(top, wts):
            g = t @ np.asarray(p["gate"][e], np.float64)
            u = t @ np.asarray(p["up"][e], np.float64)
            y = y + a * ((g / (1 + np.exp(-g))) * u) @ np.asarray(
                p["down"][e], np.float64)
        out.append(y)
    return np.asarray(out).reshape(x.shape)


@pytest.mark.parametrize("renormalize", [True, False])
def test_dropless_moe_equals_the_per_token_sum_under_skew(renormalize):
    """A router that sends every token to expert 2 first: its group is
    every token, several tiles long, and nothing is dropped."""
    key = jax.random.PRNGKey(0)
    p = _moe_params(key)
    w = np.asarray(p["router"]["w"]).copy()
    w[:, 2] += 3.0
    p["router"] = {"w": jnp.asarray(w)}
    x = jax.random.normal(jax.random.fold_in(key, 1), (3, 40, 16)) + 1.0
    _, top_e, _ = moe.route(x.reshape(-1, 16), p["router"]["w"], top_k=2)
    assert bool(jnp.all(top_e[:, 0] == 2))
    y = moe.moe_serve(p, x, top_k=2, n_experts=4, renormalize=renormalize,
                      rt=RT)
    np.testing.assert_allclose(np.asarray(y),
                               _per_token(p, x, 2, renormalize),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("quantized", [False, True])
def test_dropless_moe_ignores_batch_mates(quantized):
    """A token's output is the same alone and among 63 others."""
    key = jax.random.PRNGKey(1)
    p = _moe_params(key, d=64, f=128, e=8)
    if quantized:
        p = dict(p, **{n: quantize_weight(p[n], "sp2_4")
                       for n in ("gate", "up", "down")})
    x = jax.random.normal(jax.random.fold_in(key, 2), (64, 1, 64))
    kw = dict(top_k=2, n_experts=8, renormalize=False, rt=RT)
    full = moe.moe_serve(p, x, **kw)
    alone = jnp.concatenate([moe.moe_serve(p, x[i:i + 1], **kw)
                             for i in (0, 17, 63)])
    np.testing.assert_allclose(np.asarray(alone),
                               np.asarray(full[jnp.array([0, 17, 63])]),
                               rtol=1e-5, atol=1e-6)


# an expert-parallel serving mesh needs several devices: a subprocess with
# forced host devices, so this file keeps the single-device backend
_EXPERT_PARALLEL_WORKER = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, "src")
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.quantized import quantize_weight
from repro.launch.mesh import make_serving_mesh
from repro.nn import moe
from repro.runtime import Runtime

impl = sys.argv[1]
key = jax.random.PRNGKey(5)
p = moe.moe_init(key, 256, 256, 8)
p = dict(p, **{n: quantize_weight(p[n], "sp2_4")
               for n in ("gate", "up", "down")})
x = jax.random.normal(jax.random.fold_in(key, 1), (2, 24, 256))
kw = dict(top_k=2, n_experts=8, renormalize=False)
one = jax.jit(lambda p, x: moe.moe_serve(p, x, rt=Runtime(impl=impl),
                                         **kw))(p, x)
mesh = make_serving_mesh(model=4)
rt = Runtime(impl=impl, mesh=mesh, model_axis="model", data_axes=("data",))
held = NamedSharding(mesh, P("model"))
ps = dict(p, **{n: jax.device_put(p[n], held)
                for n in ("gate", "up", "down")})
f = jax.jit(lambda p, x: moe.moe_serve(p, x, rt=rt, **kw))
# the same sums in another layout of tiles: XLA's CPU dot may round the
# last bit of an f32 output differently, nothing more
np.testing.assert_allclose(np.asarray(f(ps, x)), np.asarray(one),
                           rtol=1e-5, atol=1e-6)
# each device reads only its two experts' codes: nothing gathers them
hlo = f.lower(ps, x).compile().as_text()
assert "all-reduce" in hlo and "all-gather" not in hlo, hlo
print("OK expert-parallel")
"""


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_dropless_moe_expert_parallel_on_a_mesh(impl):
    """On a 4-wide model axis each device computes the pairs routed to
    its own 2 of 8 SPx experts and one psum combines them: one device's
    output, to the last bit's rounding, and no all-gather of the codes."""
    r = subprocess.run([sys.executable, "-c", _EXPERT_PARALLEL_WORKER,
                        impl], capture_output=True, text=True, timeout=600,
                       cwd=str(BENCH.parent))
    assert r.returncode == 0, r.stderr[-4000:]
    assert "OK expert-parallel" in r.stdout


def test_group_by_expert_tiles_every_pair_once():
    experts = jnp.asarray([3, 0, 3, 3, 1, 3, 3, 0, 3], jnp.int32)
    dest, tile_e, tile_rows = moe.group_by_expert(experts, 4, bm=2)
    # ceil(9 / 2) + 4 tiles; expert 3's six pairs fill three whole tiles
    assert tile_e.shape == (9,)
    assert sorted(np.asarray(dest).tolist()) == sorted(set(
        np.asarray(dest).tolist()))
    for pair, row in enumerate(np.asarray(dest)):
        assert int(tile_e[row // 2]) == int(experts[pair])
        assert row % 2 < int(tile_rows[row // 2])
    assert np.asarray(tile_rows).tolist() == [2, 1, 2, 2, 2, 0, 0, 0, 0]
    # empty tiles repeat the last expert that holds rows
    assert np.asarray(tile_e).tolist() == [0, 1, 3, 3, 3, 3, 3, 3, 3]


@pytest.mark.parametrize("scheme", ["sp2_4", "spx_8_x3"])
def test_expert_kernel_interpret_matches_its_oracle(scheme):
    """Tiles of 8 rows over 4 experts of (256, 256): a full tile, partial
    ones, expert 1 holding none, and two empty tiles at the end."""
    key = jax.random.PRNGKey(3)
    w = jax.random.normal(key, (4, 256, 256)) * 0.06
    qt = quantize_weight(w, scheme)
    tile_e = jnp.asarray([0, 0, 2, 3, 3, 3], jnp.int32)
    tile_rows = jnp.asarray([8, 3, 5, 8, 0, 0], jnp.int32)
    x = jax.random.normal(jax.random.fold_in(key, 1), (48, 256))
    x = jnp.where((jnp.arange(48) % 8 < jnp.repeat(tile_rows, 8))[:, None],
                  x, 0.0)
    got = ops.spx_expert_matmul(x, qt, tile_e, tile_rows, bm=8,
                                impl="interpret")
    want = ops.spx_expert_matmul(x, qt, tile_e, tile_rows, bm=8, impl="ref")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert not np.any(np.asarray(got[32:]))
    dense = np.asarray(qt.dequantize(jnp.float32))
    np.testing.assert_allclose(np.asarray(want[24:27]),
                               np.asarray(x[24:27]) @ dense[3], rtol=1e-5,
                               atol=1e-5)


# -- Mamba norms and NoPE -----------------------------------------------------

def test_mamba_norms_only_where_the_block_has_them():
    """The flag adds the dt/B/C gains; with them the mixer's output moves,
    and the paged chunk step still continues the full scan."""
    key = jax.random.PRNGKey(4)
    plain = ssm.mamba_init(key, 32, d_state=8, dt_rank=8)
    normed = ssm.mamba_init(key, 32, d_state=8, dt_rank=8, bcdt_norm=True)
    assert {"dt_norm", "b_norm", "c_norm"} <= set(normed)
    assert not {"dt_norm", "b_norm", "c_norm"} & set(plain)
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 12, 32))
    y_plain = ssm.mamba_apply(plain, x, rt=RT)
    y_norm = ssm.mamba_apply(normed, x, rt=RT)
    assert float(jnp.max(jnp.abs(y_norm - y_plain))) > 1e-3
    assert not reduced(get_config("jamba-1.5-large-398b")).ssm_bcdt_norm
    assert reduced(get_config("jamba2-mini")).ssm_bcdt_norm
    st = {"h": jnp.zeros((2, 64, 8)), "conv": jnp.zeros((2, 3, 64))}
    outs = []
    for lo in range(0, 12, 4):
        o, st = ssm.mamba_paged_step(normed, x[:, lo:lo + 4], st,
                                     jnp.full((2,), 4, jnp.int32), rt=RT)
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, 1)),
                               np.asarray(y_norm), rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("nope", [True, False])
def test_nope_attention_ignores_positions(nope):
    cfg = dataclasses.replace(reduced(get_config("jamba2-mini")), nope=nope)
    params = lm_mod.lm_init(jax.random.PRNGKey(6), cfg)
    toks = jax.random.randint(jax.random.PRNGKey(7), (1, 12), 0,
                              cfg.vocab_size)
    pos = jnp.arange(12, dtype=jnp.int32)[None]
    a = lm_mod.lm_logits(params, toks, cfg, RT, positions=pos)
    # RoPE sees relative distance only: stretch it, not shift it
    b = lm_mod.lm_logits(params, toks, cfg, RT, positions=3 * pos)
    same = np.allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5)
    assert same == nope


@pytest.mark.parametrize("arch, precision", [("jamba2-mini", "HIGHEST"),
                                             ("qwen2-vl-2b", None)])
@pytest.mark.parametrize("fused", [False, True])
def test_serving_steps_take_the_configs_matmul_precision(arch, precision,
                                                         fused):
    """Every dot of a paged serving step, the kernels' included, runs at
    the entry's ``matmul_precision``: f32 accuracy for jamba2-mini (a bf16
    pass re-routes near-tied experts), the backend's default for qwen
    (its step is traced as before)."""
    from repro.nn.layers import quantize_params
    cfg = reduced(get_config(arch))
    params = quantize_params(lm_mod.lm_init(jax.random.PRNGKey(0), cfg),
                             "sp2_4")
    caches = lm_mod.paged_init_caches(cfg, 8, 8, dtype=jnp.float32,
                                      n_slabs=2)
    b, c = 2, 1 if fused else 4
    step = lm_mod.lm_paged_fused_step if fused else lm_mod.lm_paged_step
    jaxpr = str(jax.make_jaxpr(
        lambda *a: step(*a, cfg, Runtime(impl="interpret", q_chunk=16)))(
        params, jnp.ones((b, c), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.arange(8, dtype=jnp.int32).reshape(b, 4),
        jnp.full((b,), c, jnp.int32),
        jnp.tile(jnp.array([[0, 0]], jnp.int32), (b, 1)), caches))
    # each dot's parameters (an unstated precision is not printed)
    dots = re.findall(r"dot_general\[\n(.*?)\n\s*\]", jaxpr, re.S)
    assert dots and "pallas_call" in jaxpr
    # jamba's router states HIGHEST itself; qwen has no router
    assert {"precision=(Precision.HIGHEST" in d for d in dots} == {
        precision is not None}
