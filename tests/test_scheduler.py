"""Continuous-batching scheduler: differential request-storm replay
(FIFO synchronous engine vs cb scheduler, bit-identical greedy outputs
across the paged x SPx-quant x prefix-cache x spec-decode x prefill-chunk
matrix), fault-injected preemption at every tick-boundary class, the
run()-undrained regression, and scheduler knob validation.

The differential harness is the PR's acceptance instrument: a seeded
workload (low-priority background requests that fill the page pool, a
high-priority burst arriving mid-run that must preempt them, a straggler)
replayed through both schedulers. The cb engine preempts, offloads KV to
the host tier and resumes from the exact write cursor — and every
request's greedy output must still be byte-for-byte what the synchronous
FIFO engine produced.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import lm as lm_mod
from repro.runtime import Runtime, planner
from repro.serving.engine import Request, ServeConfig, ServeEngine

jax.config.update("jax_platform_name", "cpu")

# vocab=32 keeps top-2 logit gaps wide relative to quantization error so
# exact-output asserts don't flip on near-ties (same rationale as the
# pinned bench workload in benchmarks/serving_bench.py)
CFG = reduced(get_config("gemma-2b"), vocab=32)
RT = Runtime(impl="ref", q_chunk=16)
RT_Q = RT.replace(kv_quant=True, kv_scheme="spx_8_x3")

PAGE = 8
POOL = 8          # two background requests fill it exactly
SLOTS = 2
MAX_SEQ = 48


@pytest.fixture(scope="module")
def params():
    return lm_mod.lm_init(jax.random.PRNGKey(3), CFG)


# ---------------------------------------------------------------------------
# Seeded request storm: the shared differential workload
# ---------------------------------------------------------------------------

def _rep_tail(rng, n):
    """Repetitive token tail so the prompt-lookup drafter actually
    drafts — a fresh-random tail would make every spec combo degrade to
    plain decode and test nothing."""
    pat = rng.integers(1, CFG.vocab_size, 3).astype(np.int32)
    return np.tile(pat, -(-n // 3))[:n]


def _storm(seed=7):
    """(rid, prompt, max_new, priority, arrival_tick) tuples. Background
    requests (priority 0) reserve 4 pages each — 2 x 4 fills the 8-page
    pool — so the priority-5 burst arriving at tick 3 cannot be admitted
    without preempting one of them."""
    rng = np.random.default_rng(seed)
    sys_p = rng.integers(1, CFG.vocab_size, PAGE).astype(np.int32)

    def mk(n_tail):
        return np.concatenate([sys_p, _rep_tail(rng, n_tail)])

    reqs = [
        (0, mk(18), 6, 0, 0),       # background: 26 + 6 = 32 tok, 4 pages
        (1, mk(18), 6, 0, 0),       # background: 4 pages
        (2, mk(7), 4, 5, 3),        # burst: must preempt
        (3, mk(9), 4, 5, 3),
        (4, mk(11), 4, 5, 4),
        (5, mk(10), 4, 1, 6),       # straggler between the classes
    ]
    return [(rid, p, mn, pri, arr) for rid, p, mn, pri, arr in reqs]


def _run_fifo(params, rt):
    """The synchronous baseline: everything submitted up front in rid
    order, default knobs — the engine the tentpole replaced."""
    eng = ServeEngine(params, CFG,
                      ServeConfig(batch_slots=SLOTS, max_seq=MAX_SEQ,
                                  quantize=None, kv_layout="paged",
                                  page_size=PAGE, pool_pages=POOL,
                                  scheduler="fifo"),
                      rt=rt)
    for rid, prompt, max_new, _pri, _arr in _storm():
        eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    eng.run(max_steps=500)
    assert eng.drained
    return {r.rid: list(r.output) for r in eng.finished}


def _run_cb(params, rt, *, prefix, spec, chunk=None):
    eng = ServeEngine(params, CFG,
                      ServeConfig(batch_slots=SLOTS, max_seq=MAX_SEQ,
                                  quantize=None, kv_layout="paged",
                                  page_size=PAGE, pool_pages=POOL,
                                  scheduler="cb", prefix_cache=prefix,
                                  spec_decode=spec, spec_k=3 if spec else None,
                                  prefill_chunk=chunk),
                      rt=rt)
    pending = sorted(_storm(), key=lambda r: r[4])
    for t in range(500):
        while pending and pending[0][4] <= t:
            rid, prompt, max_new, pri, _arr = pending.pop(0)
            eng.submit(Request(rid=rid, prompt=prompt,
                               max_new_tokens=max_new, priority=pri))
        if not pending and not eng.queue \
                and all(r is None for r in eng.slot_req):
            break
        eng.step()
    else:
        pytest.fail("cb storm did not drain in 500 ticks")
    eng.pool.validate()
    return eng, {r.rid: list(r.output) for r in eng.finished}


_BASELINE = {}


def _baseline(params, kvq):
    if kvq not in _BASELINE:
        _BASELINE[kvq] = _run_fifo(params, RT_Q if kvq else RT)
    return _BASELINE[kvq]


@pytest.mark.parametrize("kvq", [False, True], ids=["f32", "spx"])
@pytest.mark.parametrize("prefix", [False, True], ids=["npx", "pfx"])
@pytest.mark.parametrize("spec", [False, True], ids=["nsp", "spec"])
# chunk 5: the 26-token background prompts prefill in 5 + 5 + 5 + 5 + 5 + 1,
# so the cb engine preempts mid-prefill and runs one-token chunks
@pytest.mark.parametrize("chunk", [None, 5], ids=["c32", "c5"])
def test_storm_differential_cb_vs_fifo(params, kvq, prefix, spec, chunk):
    """The tentpole acceptance: the same seeded storm through the old
    synchronous FIFO engine and the continuous-batching scheduler yields
    bit-identical per-request greedy outputs in every cell of the
    feature matrix — while the cb run actually preempts and offloads."""
    rt = RT_Q if kvq else RT
    eng, got = _run_cb(params, rt, prefix=prefix, spec=spec, chunk=chunk)
    assert got == _baseline(params, kvq)
    m = eng.metrics()
    assert m["preemptions"] > 0, "storm was not oversubscribed enough"
    assert m["resumes"] > 0
    assert m["offload_bytes"] > 0 and m["onload_bytes"] > 0
    assert m["offload_bytes"] == m["onload_bytes"]  # all victims resumed
    assert m["host_pages_in_use"] == 0              # drained -> host empty
    victims = [r for r in eng.finished if r.preemptions > 0]
    assert victims and all(r.priority == 0 for r in victims), \
        "only strictly-lower-priority residents may be preempted"


def test_storm_priority_ordering(params):
    """Scheduling-quality (not correctness) claims on the plain combo:
    the preempted victim resumes only after burst work drains, so the
    first burst request finishes before it; offload traffic is exactly
    the pages covering the victim's write cursor."""
    eng, _ = _run_cb(params, RT, prefix=False, spec=False)
    order = [r.rid for r in eng.finished]
    victim = next(r for r in eng.finished if r.preemptions > 0)
    burst_first = min(order.index(rid) for rid in (2, 3, 4))
    assert burst_first < order.index(victim.rid)
    # every burst request beat the straggler to admission despite the
    # straggler's earlier priority class being lower, never preempted
    straggler = next(r for r in eng.finished if r.rid == 5)
    assert straggler.preemptions == 0


# ---------------------------------------------------------------------------
# Satellite: fault-injected preemption at every tick-boundary class
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kvq", [False, True], ids=["plain", "spx"])
def test_preemption_every_tick_boundary_bit_identical(params, kvq):
    """Force a preempt/resume cycle at EVERY tick boundary of a request's
    lifetime — mid-prefill chunk, mid-spec verify window (the write
    cursor sits behind rejected-draft garbage), page-boundary write
    (cursor exactly on a page edge) — and assert the resumed output is
    bit-identical to the un-preempted run. One engine per pool flavour,
    reused across injections so the jit cache pays once."""
    rt = RT_Q if kvq else RT
    eng = ServeEngine(params, CFG,
                      ServeConfig(batch_slots=2, max_seq=48, quantize=None,
                                  kv_layout="paged", page_size=4,
                                  prefill_chunk=4, pool_pages=12,
                                  scheduler="cb", spec_decode=True, spec_k=3),
                      rt=rt)
    rng = np.random.default_rng(11)
    prompt = np.concatenate([rng.integers(1, CFG.vocab_size, 4)
                             .astype(np.int32), _rep_tail(rng, 6)])

    def run_once(rid, t_preempt):
        eng.submit(Request(rid=rid, prompt=prompt, max_new_tokens=8))
        classes = set()
        for t in range(200):
            if t == t_preempt:
                slot = next((i for i, r in enumerate(eng.slot_req)
                             if r is not None and r.rid == rid), None)
                if slot is not None:
                    fed = int(eng._fed[slot])
                    pos = int(eng.slot_pos[slot])
                    if fed >= 0:
                        classes.add("mid-prefill")
                    else:
                        classes.add("mid-spec-window")
                    if pos > 0 and pos % eng.page_size == 0:
                        classes.add("page-boundary")
                    eng.preempt(rid)
            if not eng.queue and all(r is None for r in eng.slot_req):
                break
            eng.step()
        else:
            pytest.fail("injected run did not drain")
        eng.pool.validate()
        done = {r.rid: list(r.output) for r in eng.finished}
        return done[rid], classes

    base, _ = run_once(0, -1)
    assert len(base) == 8
    covered = set()
    for t in range(1, 13):
        out, classes = run_once(100 + t, t)
        assert out == base, f"preemption at tick {t} changed the output"
        covered |= classes
    assert {"mid-prefill", "mid-spec-window", "page-boundary"} <= covered, \
        f"injection sweep missed a boundary class: {covered}"


# ---------------------------------------------------------------------------
# Satellite: run() surfaces undrained work instead of dropping it
# ---------------------------------------------------------------------------

def test_run_surfaces_undrained_work(params):
    """run() hitting max_steps with live requests used to return
    silently. Now: RuntimeError under strict (the default), drained flag
    + undrained_runs metric either way, and no work is lost — a later
    run() finishes exactly the tokens the request asked for."""
    eng = ServeEngine(params, CFG,
                      ServeConfig(batch_slots=1, max_seq=48, quantize=None,
                                  kv_layout="paged", page_size=8,
                                  prefill_chunk=4, scheduler="cb"),
                      rt=RT)
    prompt = np.arange(1, 13, dtype=np.int32)       # 3 prefill chunks
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8))
    with pytest.raises(RuntimeError, match="live work"):
        eng.run(max_steps=2)
    assert eng.drained is False
    assert eng.metrics()["undrained_runs"] == 1
    partial = eng.run(max_steps=1, strict=False)    # no raise, flagged
    assert partial == [] and eng.drained is False
    assert eng.metrics()["undrained_runs"] == 2
    done = eng.run()                                # drains clean
    assert eng.drained is True
    assert eng.metrics()["undrained_runs"] == 2
    assert len(done) == 1 and len(done[0].output) == 8


# ---------------------------------------------------------------------------
# Satellite: knob validation + the resume reservation model
# ---------------------------------------------------------------------------

def test_scheduler_knob_validation(params):
    mk = lambda **kw: ServeEngine(params, CFG,
                                  ServeConfig(batch_slots=1, max_seq=32,
                                              quantize=None, **kw),
                                  rt=RT)
    with pytest.raises(ValueError, match="fifo.*cb|'fifo' or 'cb'"):
        mk(scheduler="lifo")
    # explicit cb / tier knobs on a dense engine are caller errors
    with pytest.raises(ValueError, match="needs kv_layout='paged'"):
        mk(kv_layout="dense", scheduler="cb")
    with pytest.raises(ValueError, match="need kv_layout='paged'"):
        mk(kv_layout="dense", host_pages=4)
    with pytest.raises(ValueError, match="need kv_layout='paged'"):
        mk(kv_layout="dense", prefix_cache_pages=4)
    # dense engines run the fifo scheduler and say so
    dense = mk(kv_layout="dense")
    assert dense.scheduler == "fifo"
    assert dense.metrics()["scheduler"] == "fifo"
    # paged default is cb; preempting a non-resident rid is an error
    paged = mk(kv_layout="paged", page_size=8)
    assert paged.scheduler == "cb"
    with pytest.raises(KeyError, match="not resident"):
        paged.preempt(99)


def test_plan_resume_pages_model():
    # full reservation + restored prefix, page-rounded independently
    assert planner.plan_resume_pages(0, 32, 8) == (4, 0)
    assert planner.plan_resume_pages(9, 32, 8) == (4, 2)
    assert planner.plan_resume_pages(32, 32, 8) == (4, 4)
    with pytest.raises(ValueError):
        planner.plan_resume_pages(33, 32, 8)
    with pytest.raises(ValueError):
        planner.plan_resume_pages(-1, 32, 8)
