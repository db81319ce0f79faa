"""The ragged decode megakernel (ops.paged_decode_ragged) and the serving
engine's decode tick (docs/SERVING.md §megakernel), the one decode path:

* interpret-vs-ref kernel parity per KV scheme (dense, uniform8, sp2_8,
  spx_8_x3) across the whole ragged surface — q_len from 0 (inactive
  slot) through the full K+1 verify window, attend_len straddling page
  boundaries — plus exact zeros for padded window rows; at W == 1 the
  oracle equals plain attention over each slot's gathered context,
* ONE launch per decode tick: the decode step traces the ragged op
  exactly once, compiles exactly once, and never retraces across ticks
  with varying attend_len / n_valid (no pow2-window padding to bucket
  on), with or without speculation,
* engine outputs against independent oracles: the dense engine (greedy,
  speculative, seeded sampling), a one-token final prefill chunk, and
  enc-dec speculation through the decode program's K+1 window,
* planner sizing (codes+scale pages + resident LUT) and the autotune key
  separating kv_scheme and the spec window,
* knobs: ``fused_decode`` has one value left; False is an error and the
  removed ops and environment variable are gone.

No hypothesis dependency — collected on the bare tier-1 environment.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.core import spx
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.models import encdec as encdec_mod
from repro.models import lm as lm_mod
from repro.nn.attention import dequantize_kv, quantize_pages
from repro.runtime import Runtime, planner
from repro.serving.engine import Request, ServeConfig, ServeEngine

jax.config.update("jax_platform_name", "cpu")

RT = Runtime(impl="ref", q_chunk=16)

SCHEMES = (None, "uniform8", "sp2_8", "spx_8_x3")


# ---------------------------------------------------------------------------
# Kernel parity: interpret (the Pallas body on CPU) vs the jnp oracle
# ---------------------------------------------------------------------------

def _pools(rng, b, hkv, ps, max_pages, dh, scheme):
    """Random page pools (+1 spare page so block tables can alias), the
    block tables, and the (k, v) pool pair in the layout ``scheme`` asks
    for (dense arrays, or codes+scale dicts)."""
    n_pages = 1 + b * max_pages
    kp = jnp.asarray(rng.standard_normal((n_pages, hkv, ps, dh)),
                     jnp.float32)
    vp = jnp.asarray(rng.standard_normal((n_pages, hkv, ps, dh)),
                     jnp.float32)
    bt = jnp.asarray(rng.integers(0, n_pages, (b, max_pages)), jnp.int32)
    if scheme is None:
        return kp, vp, bt
    return quantize_pages(kp, scheme), quantize_pages(vp, scheme), bt


def _stacked(kp, vp):
    """The (k, v) pools as the ragged op takes them: every layer's pools
    on a leading axis, here one layer (read at ``layer=0``)."""
    return jax.tree_util.tree_map(lambda a: a[None], (kp, vp))


# dh=128 tiles take the lane-gather lookup for 8-bit codes, dh=32 the
# select chain (kernels/decode.py)
@pytest.mark.parametrize("dh", [32, 128])
@pytest.mark.parametrize("scheme", SCHEMES)
def test_ragged_interpret_matches_ref(scheme, dh):
    rng = np.random.default_rng(11)
    b, hq, hkv, ps, mp, w = 4, 4, 2, 8, 4, 4   # verify window K+1=4
    kp, vp, bt = _pools(rng, b, hkv, ps, mp, dh, scheme)
    q = jnp.asarray(rng.standard_normal((b, w, hq, dh)), jnp.float32)
    # ragged surface: q_len 0 (inactive) .. w (full window); ctx at and
    # around a page boundary so the per-slot trip count changes mid-batch
    ctx = jnp.asarray([0, ps - 1, ps, ps + 1], jnp.int32)
    qlen = jnp.asarray([0, 1, 3, w], jnp.int32)
    kw = dict(kv_scheme=scheme) if scheme else {}
    want = ops.paged_decode_ragged(q, *_stacked(kp, vp), bt, ctx, qlen,
                                   layer=0, impl="ref", **kw)
    got = ops.paged_decode_ragged(q, *_stacked(kp, vp), bt, ctx, qlen,
                                  layer=0, impl="interpret", **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
    # rows past q_len (and the whole inactive slot 0) are EXACT zeros in
    # both impls — the engine relies on never reading garbage there
    for out in (np.asarray(want), np.asarray(got)):
        assert (out[0] == 0).all()
        assert (out[1, 1:] == 0).all()
        assert (out[2, 3:] == 0).all()
        assert (out[3] != 0).any()


def _gathered(pages, bt, scheme):
    """Each slot's pages of ``pages`` in logical order, (B, Hkv, S, dh),
    dequantized when ``scheme`` names one."""
    if scheme is None:
        b, mp = bt.shape
        hkv, ps, dh = pages.shape[1:]
        return jnp.moveaxis(pages[bt], 2, 1).reshape(b, hkv, mp * ps, dh)
    codes = _gathered(pages["codes"], bt, None)
    scale = kref.page_scales(pages["scale"][bt], pages["codes"].shape[2])
    return dequantize_kv(codes, scale, scheme, dtype=jnp.float32)


@pytest.mark.parametrize("scheme", [None, "spx_8_x3"], ids=["f32", "spx"])
def test_ragged_w1_matches_attention_over_gathered_context(scheme):
    """W == 1 is plain decode: the ragged ref equals plain softmax
    attention (``ref.attention_ref``) of the one query over each slot's
    gathered context, cut at its length (attend_len = ctx + 1)."""
    rng = np.random.default_rng(5)
    b, hq, hkv, dh, ps, mp = 3, 4, 2, 32, 8, 3
    kp, vp, bt = _pools(rng, b, hkv, ps, mp, dh, scheme)
    q = jnp.asarray(rng.standard_normal((b, 1, hq, dh)), jnp.float32)
    ctx = [ps - 1, ps, 2 * ps + 3]
    ones = jnp.ones((b,), jnp.int32)
    kw = dict(kv_scheme=scheme) if scheme else {}
    ragged = ops.paged_decode_ragged(q, *_stacked(kp, vp), bt,
                                     jnp.asarray(ctx, jnp.int32), ones,
                                     layer=0, impl="ref", **kw)
    k, v = _gathered(kp, bt, scheme), _gathered(vp, bt, scheme)
    rep = hq // hkv
    for i, n in enumerate(ctx):
        # query head h reads KV head h // rep
        ki = jnp.repeat(k[i, :, :n + 1], rep, axis=0)
        vi = jnp.repeat(v[i, :, :n + 1], rep, axis=0)
        want = kref.attention_ref(q[i, 0][:, None], ki, vi, causal=False)
        np.testing.assert_allclose(np.asarray(ragged[i, 0]),
                                   np.asarray(want[:, 0]),
                                   rtol=1e-5, atol=1e-5)


def test_ragged_quant_needs_scheme():
    rng = np.random.default_rng(0)
    kq, vq, bt = _pools(rng, 2, 1, 8, 2, 16, "uniform8")
    q = jnp.zeros((2, 1, 2, 16), jnp.float32)
    lens = jnp.ones((2,), jnp.int32)
    with pytest.raises(ValueError, match="kv_scheme"):
        ops.paged_decode_ragged(q, *_stacked(kq, vq), bt, lens, lens,
                                layer=0, impl="ref")


def test_registry_has_ragged_ops():
    from repro.runtime import registry
    for op in ("paged_decode_ragged", "paged_decode_ragged_quant"):
        assert set(registry.available_impls(op)) >= {"ref", "interpret"}
        assert registry.resolve(op, "auto").impl == "ref"   # CPU


@pytest.mark.parametrize("op", ["paged_attention", "paged_attention_quant"])
def test_single_token_decode_ops_are_gone(op):
    """The per-call single-token decode ops were removed: neither the
    registry nor ``ops`` knows them, while the ragged ops resolve."""
    from repro.runtime import registry
    assert op not in registry.registered_ops()
    assert registry.available_impls(op) == ()
    with pytest.raises(registry.KernelUnavailable):
        registry.resolve(op, "auto")
    assert not hasattr(ops, op)
    ragged = op.replace("paged_attention", "paged_decode_ragged")
    assert registry.resolve(ragged, "ref").op == ragged


# ---------------------------------------------------------------------------
# One launch per tick + the pow2-padding retrace hazard
# ---------------------------------------------------------------------------

def _tiny_cfg():
    # pinned exact-greedy workload (see tests/test_spec_decode.py): vocab
    # 32 keeps random-init top-2 logit gaps wide, so equality assertions
    # compare decode paths instead of coin-flip near-ties
    return dataclasses.replace(reduced(get_config("gemma-2b"), vocab=32),
                               head_dim=128)


def test_fused_step_single_trace_across_ragged_ticks():
    """The megakernel step compiles ONCE and traces the ragged attention
    op ONCE — varying attend_len / n_valid across ticks rides in the
    scalar-prefetch data, not the trace, so there is no pow2 bucketing
    and no retrace (the Runtime-test discipline, now for raggedness)."""
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    caches = lm_mod.paged_init_caches(cfg, n_pages=8, page_size=8,
                                      dtype=jnp.float32)
    step = jax.jit(lm_mod.lm_paged_fused_step, static_argnums=(7, 8))
    bt = jnp.arange(8, dtype=jnp.int32).reshape(2, 4)
    w = 4
    tokens = jnp.zeros((2, w), jnp.int32)
    sidx = jnp.zeros((2, 2), jnp.int32)               # attn-only sentinels
    ops.reset_op_calls()
    ticks = [([3, 9], [1, 4]), ([4, 13], [4, 1]),     # ragged + page
             ([8, 14], [2, 3]), ([0, 17], [0, 2])]    # boundary crossings
    for ctx, nv in ticks:
        logits, caches = step(params, tokens, jnp.asarray(ctx, jnp.int32),
                              bt, jnp.asarray(nv, jnp.int32), sidx, caches,
                              cfg, RT)
    assert logits.shape == (2, w, cfg.vocab_size)
    assert step._cache_size() == 1                    # zero retrace
    calls = ops.op_calls()
    # one trace, one ragged-op call site inside it (the layer scan traces
    # its body once)
    assert calls.get("paged_decode_ragged") == 1


def _pallas_kernels(jaxpr) -> list[str]:
    """Source names of the Pallas kernels a jaxpr launches, nested
    jaxprs (the layer scan's body) included."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            src = eqn.params["jaxpr"].debug_info.func_src_info
            out.append(src.split()[0])
            continue
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    out += _pallas_kernels(sub)
    return out


def test_engine_fused_tick_is_one_compile_one_launch():
    """Whatever earlier tests traced in this process: the engine's fused
    step holds one ragged kernel launch, the engine launches it once per
    decode tick, and ticks after a warm one neither trace nor compile
    it again, though attend_len and n_valid vary from tick to tick."""
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    eng = ServeEngine(params, cfg,
                      ServeConfig(batch_slots=2, max_seq=64, quantize=None,
                                  kv_layout="paged", fused_decode=True),
                      rt=RT)
    # the step's program, traced with the Pallas kernels in place of the
    # jnp oracles: the in-place KV write and the ragged decode (the layer
    # scan traces its body once)
    b = eng.batch_slots
    traced = jax.jit(lm_mod.lm_paged_fused_step, static_argnums=(7, 8)).trace(
        eng.params, jnp.zeros((b, 1), jnp.int32), jnp.zeros(b, jnp.int32),
        jnp.asarray(eng.block_tables), jnp.ones(b, jnp.int32),
        jnp.asarray(eng._state_idx), eng.caches, cfg,
        dataclasses.replace(RT, impl="interpret"))
    assert _pallas_kernels(traced.jaxpr.jaxpr) == ["_kv_write_kernel",
                                                   "_ragged_kernel"]

    rng = np.random.default_rng(3)
    reqs = [Request(rid=i, prompt=rng.integers(
        0, cfg.vocab_size, int(rng.integers(4, 24))).astype(np.int32),
        max_new_tokens=6) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    eng.step()                                  # warm: prefill + decode
    assert eng.metrics()["launches"] == {"prefill": 1, "decode": 1}
    events = []

    def on_event(event, duration, **kw):
        if "lm_paged_fused_step" in str(kw.get("fun_name", "")):
            events.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_event)
    decode_ticks = 1
    try:
        # ragged prompt lengths + continuous batching vary attend_len and
        # n_valid across ticks; a tick decodes where it emits more tokens
        # than first tokens, and then launches the fused step once
        while eng.has_work():
            before = [len(r.output) for r in reqs]
            launched = eng.metrics()["launches"]["decode"]
            eng.step()
            firsts = sum(1 for r, n in zip(reqs, before)
                         if n == 0 and r.output)
            decoded = sum(len(r.output) for r in reqs) - sum(before) \
                - firsts
            launched = eng.metrics()["launches"]["decode"] - launched
            assert launched == (1 if decoded else 0)
            decode_ticks += launched
    finally:
        jax.monitoring.unregister_event_duration_listener(on_event)
    # ... and nothing traced or compiled the fused step after the warm
    # tick
    assert events == []
    m = eng.metrics()
    assert m["launches"]["decode"] == decode_ticks > 2
    assert m["model_calls"] == sum(m["launches"].values())


# ---------------------------------------------------------------------------
# Engine outputs on the one decode path, against independent oracles
# ---------------------------------------------------------------------------

SPX_RT = RT.replace(kv_quant=True, kv_scheme="spx_8_x3")


def _engine(params, cfg, *, layout="paged", rt=RT, **kw):
    return ServeEngine(params, cfg,
                       ServeConfig(batch_slots=2, max_seq=64, quantize=None,
                                   kv_layout=layout, **kw), rt=rt)


def _run(eng, prompts, *, new_tokens=8, temperature=0.0, frames=None):
    """{rid: output} of ``prompts`` served to the end on ``eng``."""
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens,
                           temperature=temperature,
                           seed=17 + i if temperature else None,
                           frames=None if frames is None else frames[i]))
    return {r.rid: list(r.output) for r in eng.run()}


def _widths(eng, kind: str) -> list[int]:
    """The token width of each ``kind`` launch ("prefill" or "decode")
    ``eng`` makes from now on."""
    widths = []
    launch = eng._paged_launch

    def spy(k, step, width, feed):
        if k == kind:
            widths.append(width)
        return launch(k, step, width, feed)

    eng._paged_launch = spy
    return widths


def _repetitive(cfg, n=4, reps=3, seed=3):
    # tiled motifs: the prompt-lookup drafter has something to propose
    rng = np.random.default_rng(seed)
    return [np.tile(rng.integers(1, cfg.vocab_size, 4).astype(np.int32),
                    reps) for _ in range(n)]


@pytest.mark.parametrize("kv", ["f32", "spx"])
def test_one_token_prefill_chunk(kv):
    """Prompts of length 1 (mod prefill_chunk) end in a one-token chunk:
    a tick whose prefilling slots all hold one prompt token runs at
    C = 1, through the chunk gather like every other prefill chunk.
    Greedy outputs equal the dense engine's (f32 KV) or, with SPx KV,
    the same engine's at a chunk size where no tick runs at C = 1."""
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(4)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (9, 9, 17, 25)]
    rt = SPX_RT if kv == "spx" else RT
    eng = _engine(params, cfg, rt=rt, prefill_chunk=8)
    widths = _widths(eng, "prefill")
    got = _run(eng, prompts)
    assert 1 in widths
    if kv == "f32":
        want = _run(_engine(params, cfg, layout="dense"), prompts)
    else:
        oracle = _engine(params, cfg, rt=rt, prefill_chunk=32)
        oracle_widths = _widths(oracle, "prefill")
        want = _run(oracle, prompts)
        assert 1 not in oracle_widths
    assert got == want


@pytest.mark.parametrize("kv", ["f32", "spx"])
def test_encdec_spec_matches_nonspec(kv):
    """Enc-dec speculation scores its K+1 window through the decode
    program (``encdec_paged_fused_step``): the self-attention in the
    ragged decode kernel, cross-attention over the shared encoder
    entry. Greedy outputs equal speculation off."""
    cfg = reduced(get_config("whisper-small"), vocab=32)
    params = encdec_mod.encdec_init(jax.random.PRNGKey(2), cfg)
    fr = np.asarray(jax.random.normal(
        jax.random.PRNGKey(3), (2, cfg.enc_seq_len, cfg.d_model)))
    frames = [fr[0], fr[1], fr[0]]
    prompts = _repetitive(cfg, n=3)
    rt = SPX_RT if kv == "spx" else RT
    off = _run(_engine(params, cfg, rt=rt), prompts, frames=frames)
    eng = _engine(params, cfg, rt=rt, spec_decode=True, spec_k=3)
    on = _run(eng, prompts, frames=frames)
    assert on == off
    assert eng._spec_proposed > 0          # the windows carried drafts


@pytest.mark.parametrize("prefix", [False, True], ids=["npx", "pfx"])
def test_paged_spec_greedy_matches_dense(prefix):
    """Speculative greedy decode on the paged engine — the K+1 window
    through the ragged decode kernel, with shared prefix pages or
    without — emits what the dense engine emits without speculation."""
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    sys_p = np.random.default_rng(8).integers(1, cfg.vocab_size, 8)
    prompts = [np.concatenate([sys_p, p]).astype(np.int32)
               for p in _repetitive(cfg)]
    want = _run(_engine(params, cfg, layout="dense"), prompts)
    eng = _engine(params, cfg, page_size=8, prefix_cache=prefix,
                  spec_decode=True, spec_k=3)
    assert _run(eng, prompts) == want
    m = eng.metrics()
    assert m["draft_acceptance_rate"] > 0
    assert (m["prefix_hits"] > 0) == prefix


def test_sampled_matches_dense_key_chain():
    """Temperature sampling: the paged decode tick draws from each
    request's key chain exactly as the dense engine does (one draw per
    emitted token), so seeded sampled outputs are identical."""
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(0, cfg.vocab_size, 8).astype(np.int32)
               for _ in range(2)]
    paged = _run(_engine(params, cfg), prompts, new_tokens=6,
                 temperature=0.8)
    dense = _run(_engine(params, cfg, layout="dense"), prompts,
                 new_tokens=6, temperature=0.8)
    assert paged == dense


class _AlternateDrafter:
    """Proposes ``k`` copies of the last token on every other tick and
    nothing in between, so drafted and undrafted ticks alternate."""

    def __init__(self):
        self.history: dict[int, list[int]] = {}

    def start(self, rid, prompt):
        self.history[rid] = [int(t) for t in prompt]

    def extend(self, rid, tok):
        self.history[rid].append(int(tok))

    def drop(self, rid):
        self.history.pop(rid, None)

    def propose(self, rid, k):
        h = self.history[rid]
        return [h[-1]] * k if len(h) % 2 else []


def test_spec_ticks_share_one_decode_program():
    """With speculation on, drafted and undrafted ticks launch the same
    decode program at the same width W = spec_k + 1 — so one compile
    serves both, and there is no launch but prefill and decode — and
    greedy outputs equal speculation off."""
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    prompts = _repetitive(cfg, n=1)
    want = _run(_engine(params, cfg), prompts, new_tokens=10)
    eng = _engine(params, cfg, spec_decode=True, spec_k=3)
    eng.drafter = _AlternateDrafter()
    widths = _widths(eng, "decode")
    assert _run(eng, prompts, new_tokens=10) == want
    m = eng.metrics()
    assert set(m["launches"]) == {"prefill", "decode"}
    assert 0 < eng._spec_windows < m["launches"]["decode"]
    assert widths == [4] * m["launches"]["decode"]


# ---------------------------------------------------------------------------
# Knobs
# ---------------------------------------------------------------------------

def test_fused_decode_knobs(monkeypatch):
    """One decode path: the removed REPRO_FUSED_DECODE=0 changes nothing,
    fused_decode=False is an error, and a dense engine still resolves."""
    cfg = _tiny_cfg()
    params = lm_mod.lm_init(jax.random.PRNGKey(3), cfg)
    prompts = _repetitive(cfg, n=1)
    for env in (None, "0"):
        if env is not None:
            monkeypatch.setenv("REPRO_FUSED_DECODE", env)
        eng = _engine(params, cfg)
        assert eng.config.fused_decode is True
        _run(eng, prompts, new_tokens=3)
        m = eng.metrics()
        assert m["launches"] == {"prefill": 1, "decode": 2}
        assert "fused_decode" not in m
    monkeypatch.delenv("REPRO_FUSED_DECODE")
    assert ServeConfig(quantize=None, fused_decode=True).resolve(cfg) \
        == ServeConfig(quantize=None).resolve(cfg)
    with pytest.raises(ValueError, match="fused_decode=False"):
        ServeConfig(quantize=None, fused_decode=False).resolve(cfg)
    # the dense engine resolves, and so does its replace()d config
    dense = _engine(params, cfg, layout="dense")
    again = dense.config.replace(batch_slots=4).resolve(cfg)
    assert again.kv_layout == "dense" and again.resolved


# ---------------------------------------------------------------------------
# Planner model + autotune key
# ---------------------------------------------------------------------------

def test_plan_fused_decode_byte_model():
    dense = planner.plan_fused_decode(128, rep=4, w=5, page_size=16,
                                      act_bytes=4)
    quant = planner.plan_fused_decode(128, rep=4, w=5, page_size=16,
                                      act_bytes=4, kv_scheme="spx_8_x3")
    assert dense.rows == quant.rows == 20
    assert dense.lut_bytes == 0
    # 8-bit code schemes: 256-entry f32 LUT resident for the launch
    assert quant.lut_bytes == 4 * 256
    # codes+scale pages stream fewer bytes than f32 pages, so the quant
    # kernel's margin is strictly better at the same window
    assert quant.margin > dense.margin
    assert dense.vmem_bytes > 0 and quant.vmem_bytes > 0
    # a wider window adds compute per streamed page, never load
    w1 = planner.plan_fused_decode(128, rep=4, w=1, page_size=16,
                                   act_bytes=4)
    assert dense.margin > w1.margin


def test_fused_decode_key_separates_scheme_and_window():
    base = dict(b=4, hkv=2, rep=4, dh=128, page_size=16, max_pages=8)
    k_dense = planner.fused_decode_key(w=1, kv_scheme=None, **base)
    k_quant = planner.fused_decode_key(w=1, kv_scheme="spx_8_x3", **base)
    k_verify = planner.fused_decode_key(w=5, kv_scheme=None, **base)
    k_uniform = planner.fused_decode_key(w=1, kv_scheme="uniform8", **base)
    assert len({k_dense, k_quant, k_verify, k_uniform}) == 4
    # and the measured-plan table keys on it: a winner cached for one
    # scheme/window is invisible to the others
    planner.clear_plan_cache()
    plan = planner.plan_fused_decode(128, rep=4, w=1, page_size=16)
    assert planner.measured_best(k_dense, [plan], lambda p: 1.0) is plan
    assert planner.measured_plan(k_dense) is plan
    assert planner.measured_plan(k_quant) is None
    assert planner.measured_plan(k_verify) is None
    planner.clear_plan_cache()
