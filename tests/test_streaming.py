"""Per-request token streaming + cancellation (serving/stream.py):
stream-vs-run() bit-identity across the paged x SPx x spec x scheduler
matrix, cancellation at every tick-boundary class with a clean pool
``validate()`` after each, the monotonic fake-clock regression, the
submit-reuse regression, the strict-run stream sentinel, and the asyncio
consumption path.
"""
import asyncio

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import lm as lm_mod
from repro.runtime import Runtime
from repro.serving import engine as engine_mod
from repro.serving.engine import Request, ServeConfig, ServeEngine
from repro.serving.kv_cache import StateCache
from repro.serving.stream import StreamCancelled, StreamError

jax.config.update("jax_platform_name", "cpu")

# same pinned geometry as tests/test_scheduler.py: vocab=32 keeps top-2
# logit gaps wide so exact-output asserts don't flip on near-ties
CFG = reduced(get_config("gemma-2b"), vocab=32)
RT = Runtime(impl="ref", q_chunk=16)
RT_Q = RT.replace(kv_quant=True, kv_scheme="spx_8_x3")

PAGE = 8
POOL = 8
SLOTS = 2
MAX_SEQ = 48


@pytest.fixture(scope="module")
def params():
    return lm_mod.lm_init(jax.random.PRNGKey(3), CFG)


def _prompts(seed=3, n=4):
    """Mixed-length prompts with repetitive tails, so spec combos
    actually draft instead of degrading to plain decode."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n):
        pat = rng.integers(1, CFG.vocab_size, 3).astype(np.int32)
        out.append(np.tile(pat, int(rng.integers(2, 5))))
    return out


def _engine(params, *, kvq=False, prefix=False, spec=False,
            scheduler="cb", layout="paged"):
    return ServeEngine(params, CFG,
                       ServeConfig(batch_slots=SLOTS, max_seq=MAX_SEQ,
                                   quantize=None, kv_layout=layout,
                                   **({} if layout == "dense"
                                      else dict(page_size=PAGE,
                                                pool_pages=POOL,
                                                scheduler=scheduler,
                                                prefix_cache=prefix,
                                                spec_decode=spec,
                                                spec_k=3 if spec else None))),
                       rt=RT_Q if kvq else RT)


def _submit_all(eng, new_tokens=6):
    for i, p in enumerate(_prompts()):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=new_tokens))


# ---------------------------------------------------------------------------
# Stream-vs-run() bit-identity across the feature matrix
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kvq,prefix,spec,scheduler", [
    (False, False, False, "cb"),
    (False, False, False, "fifo"),
    (True, False, False, "cb"),
    (False, True, False, "cb"),
    (False, False, True, "cb"),
    (False, False, True, "fifo"),
    (True, True, True, "cb"),
])
def test_stream_matches_run(params, kvq, prefix, spec, scheduler):
    """Delivered token sequences are bit-identical to run() results —
    streams read Request.output behind a cursor, so this pins that the
    read path stays pure across every engine feature combination."""
    ref = _engine(params, kvq=kvq, prefix=prefix, spec=spec,
                  scheduler=scheduler)
    _submit_all(ref)
    base = {r.rid: list(r.output) for r in ref.run(max_steps=500)}

    eng = _engine(params, kvq=kvq, prefix=prefix, spec=spec,
                  scheduler=scheduler)
    _submit_all(eng)
    streams = {i: eng.stream(i) for i in range(4)}
    # interleaved consumption: one token per stream round-robin, so the
    # consumers pull across requests while the engine is mid-flight
    got = {i: [] for i in range(4)}
    live = set(got)
    while live:
        for i in sorted(live):
            try:
                got[i].append(next(streams[i]))
            except StopIteration:
                live.discard(i)
    assert got == base
    eng.pool.validate()
    # a second stream over a finished request replays the full output
    assert list(eng.stream(2)) == base[2]


def test_stream_matches_run_dense(params):
    """The delivery surface is layout-agnostic: dense engines stream
    through the same state machine."""
    ref = _engine(params, layout="dense")
    _submit_all(ref)
    base = {r.rid: list(r.output) for r in ref.run(max_steps=500)}
    eng = _engine(params, layout="dense")
    _submit_all(eng)
    assert {i: list(eng.stream(i)) for i in range(4)} == base


def test_stream_unknown_rid(params):
    eng = _engine(params)
    with pytest.raises(KeyError):
        eng.stream(99)


# ---------------------------------------------------------------------------
# Cancellation at every tick-boundary class
# ---------------------------------------------------------------------------

def _assert_clean(eng):
    """No leaked pages/slabs/host entries after everything drained."""
    eng.pool.validate()
    st = eng.pool.stats
    assert st.pages_in_use == 0
    assert st.slabs_in_use == 0
    assert st.host_pages_in_use == 0


def test_cancel_queued(params):
    """Cancel a request still waiting in the queue (never admitted)."""
    eng = _engine(params)
    _submit_all(eng)                    # 4 requests through 2 slots
    assert eng.cancel(3) is True        # back of the queue
    eng.pool.validate()
    done = eng.run(max_steps=500)
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert eng.metrics()["requests_cancelled"] == 1
    _assert_clean(eng)
    with pytest.raises(StreamCancelled):
        list(eng.stream(3))


def test_cancel_mid_prefill(params):
    """Cancel a resident slot that is still feeding prompt chunks."""
    eng = ServeEngine(params, CFG,
                      ServeConfig(batch_slots=SLOTS, max_seq=MAX_SEQ,
                                  quantize=None, kv_layout="paged",
                                  page_size=PAGE, pool_pages=POOL,
                                  prefill_chunk=4),
                      rt=RT)
    rng = np.random.default_rng(0)
    long_prompt = rng.integers(1, CFG.vocab_size, 20).astype(np.int32)
    eng.submit(Request(rid=0, prompt=long_prompt, max_new_tokens=4))
    eng.step()                          # one 4-token chunk of 20 fed
    assert eng._fed[0] >= 0, "request should still be prefilling"
    assert eng.cancel(0) is True
    _assert_clean(eng)
    assert not eng.has_work()


def test_cancel_mid_decode_and_verify(params):
    """Cancel requests that already emitted tokens — one on a plain
    decode engine, one mid-verify on a speculative engine."""
    for spec in (False, True):
        eng = _engine(params, spec=spec)
        _submit_all(eng, new_tokens=8)
        while not any(len(r.output) for r in eng.slot_req
                      if r is not None):
            eng.step()
        rid = next(r.rid for r in eng.slot_req
                   if r is not None and len(r.output))
        assert eng.cancel(rid) is True
        eng.pool.validate()
        done = eng.run(max_steps=500)
        assert rid not in {r.rid for r in done}
        _assert_clean(eng)


def test_cancel_preempted_and_parked(params):
    """Cancel a request parked on the host tier mid-preemption: the
    host entry (snapshot payload + page accounting) must drop."""
    eng = _engine(params)
    _submit_all(eng, new_tokens=8)
    while not any(len(r.output) for r in eng.slot_req if r is not None):
        eng.step()
    rid = next(r.rid for r in eng.slot_req
               if r is not None and len(r.output))
    eng.preempt(rid)                    # fault injection: park it
    assert eng.pool.host_resident(rid)
    assert eng.pool.stats.host_pages_in_use > 0
    assert eng.cancel(rid) is True
    assert not eng.pool.host_resident(rid)
    assert eng.pool.stats.host_pages_in_use == 0
    eng.pool.validate()
    done = eng.run(max_steps=500)
    assert rid not in {r.rid for r in done}
    _assert_clean(eng)


def test_cancel_terminal_and_unknown(params):
    eng = _engine(params)
    _submit_all(eng)
    eng.run(max_steps=500)
    assert eng.cancel(0) is False       # already finished
    with pytest.raises(KeyError):
        eng.cancel(99)                  # never submitted
    eng2 = _engine(params)
    _submit_all(eng2)
    assert eng2.cancel(1) is True
    assert eng2.cancel(1) is False      # double cancel: no live work


def test_drop_host_pool_level():
    """StateCache.drop_host releases the host entry AND the cross
    reference offload deliberately retained (a parked sequence keeps
    its share of the encoder output; a cancelled one must not)."""
    pool = StateCache(8, 4, n_slabs=2, n_cross=2, host_pages=8)
    key = b"frames-0"
    assert pool.allocate(0, 8, need_slab=True, cross_key=key) is not None
    assert pool.allocate(1, 8, need_slab=True, cross_key=key) is not None
    assert pool.stats.cross_in_use == 1          # shared entry
    assert pool.offload(0, 2, payload="snap") is not None
    assert pool.seq_cross(0) is not None         # ref survives parking
    assert pool.stats.slabs_in_use == 1          # slab went back
    pool.validate()
    assert pool.drop_host(0) == 2
    assert pool.seq_cross(0) is None
    assert pool.stats.host_pages_in_use == 0
    assert pool.stats.cross_in_use == 1          # seq 1 still holds it
    pool.validate()
    pool.release(1)
    assert pool.stats.cross_in_use == 0          # cached-free now
    pool.validate()
    with pytest.raises(KeyError):
        pool.drop_host(0)                        # not parked anymore


# ---------------------------------------------------------------------------
# Monotonic clock: fake-clock regression + no wall-clock in the suite
# ---------------------------------------------------------------------------

def test_fake_clock_latencies(params, monkeypatch):
    """Every engine timestamp flows through the engine._now hook: under
    a fake counter clock the latency metrics are exact tick counts —
    and can never go negative, the bug wall-clock time.time() had."""
    t = {"now": 0.0}

    def fake_now():
        t["now"] += 1.0
        return t["now"]

    monkeypatch.setattr(engine_mod, "_now", fake_now)
    eng = _engine(params)
    _submit_all(eng)
    done = eng.run(max_steps=500)
    assert len(done) == 4
    for r in done:
        assert r.t_enqueue > 0
        assert r.t_first_token > r.t_enqueue
        assert r.t_done >= r.t_first_token
    m = eng.metrics()
    assert m["ttft_p50_ms"] > 0
    assert m["latency_p95_ms"] >= m["latency_p50_ms"] > 0
    assert m["wall_s"] > 0


def test_default_clock_is_monotonic():
    import time
    assert engine_mod._now is time.monotonic


def test_no_wall_clock_in_timing_code():
    """No metric in the suite may derive from time.time(): scan every
    timing-bearing source tree for the call (comments excluded)."""
    import os
    root = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
    offenders = []
    for sub in ("src/repro/serving", "src/repro/launch",
                "src/repro/training", "benchmarks", "examples"):
        for dirpath, _dirs, files in os.walk(os.path.join(root, sub)):
            for fn in files:
                if not fn.endswith(".py"):
                    continue
                path = os.path.join(dirpath, fn)
                with open(path) as fh:
                    for ln, line in enumerate(fh, 1):
                        if line.split("#", 1)[0].count("time.time()"):
                            offenders.append(f"{path}:{ln}")
    assert not offenders, f"wall-clock timing sites: {offenders}"


# ---------------------------------------------------------------------------
# submit() reuse hardening
# ---------------------------------------------------------------------------

def test_submit_rejects_served_request_object(params):
    eng = _engine(params)
    _submit_all(eng)
    done = eng.run(max_steps=500)
    with pytest.raises(ValueError, match="already .* served|already"):
        eng.submit(done[0])             # stale PRNG chain + timestamps


def test_submit_rejects_finished_rid(params):
    eng = _engine(params)
    _submit_all(eng)
    eng.run(max_steps=500)
    with pytest.raises(ValueError, match="finished"):
        eng.submit(Request(rid=0, prompt=_prompts()[0],
                           max_new_tokens=4))
    # the benchmark warmup pattern stays legal: reset, then fresh
    # Request objects may reuse the rids
    eng.reset_metrics()
    eng.submit(Request(rid=0, prompt=_prompts()[0], max_new_tokens=4))
    assert len(eng.run(max_steps=500)) == 1


def test_resubmit_after_cancel_gets_fresh_stream(params):
    """A cancelled rid may be resubmitted (fresh Request object): the
    new submission binds a new stream state, and streams opened on the
    cancelled one stay terminal."""
    eng = _engine(params)
    _submit_all(eng)
    eng.cancel(3)
    old = eng.stream(3)
    eng.submit(Request(rid=3, prompt=_prompts()[3], max_new_tokens=6))
    done = eng.run(max_steps=500)
    assert 3 in {r.rid for r in done}
    assert len(list(eng.stream(3))) == 6     # the new request's tokens
    with pytest.raises(StreamCancelled):
        list(old)                            # the old state is terminal


# ---------------------------------------------------------------------------
# strict-run stream sentinel
# ---------------------------------------------------------------------------

def test_strict_run_fails_streams(params):
    """run(strict=True) hitting max_steps with live work must leave
    pending streams in a terminal error state, not hanging forever."""
    eng = _engine(params)
    _submit_all(eng, new_tokens=16)
    s = eng.stream(0)
    with pytest.raises(RuntimeError, match="live work"):
        eng.run(max_steps=2)
    with pytest.raises(StreamError):
        list(s)
    # the error state also wakes async consumers
    async def consume():
        async for _ in eng.stream(1):
            pass
    with pytest.raises(StreamError):
        asyncio.run(consume())


# ---------------------------------------------------------------------------
# asyncio consumption
# ---------------------------------------------------------------------------

def test_async_stream_matches_run(params):
    ref = _engine(params)
    _submit_all(ref)
    base = {r.rid: list(r.output) for r in ref.run(max_steps=500)}

    eng = _engine(params)

    async def amain():
        _submit_all(eng)

        async def consume(i):
            toks = []
            async for tok in eng.stream(i):
                toks.append(tok)
            return toks

        async def drive():
            while eng.has_work():
                eng.step()
                await asyncio.sleep(0)

        res = await asyncio.gather(drive(),
                                   *[consume(i) for i in range(4)])
        return {i: res[1 + i] for i in range(4)}

    assert asyncio.run(amain()) == base


def test_async_cancel_wakes_consumer(params):
    eng = _engine(params)

    async def amain():
        _submit_all(eng)

        async def consume():
            async for _ in eng.stream(3):
                pass

        task = asyncio.ensure_future(consume())
        await asyncio.sleep(0)          # let the consumer park
        eng.cancel(3)
        with pytest.raises(StreamCancelled):
            await task
        while eng.has_work():
            eng.step()
            await asyncio.sleep(0)

    asyncio.run(amain())
    assert sorted(r.rid for r in eng.finished) == [0, 1, 2]
    _assert_clean(eng)
