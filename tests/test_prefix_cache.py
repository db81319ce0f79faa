"""Shared-prefix KV page reuse: PagePool refcount/prefix-index semantics,
allocation-failure atomicity, seeded randomized pool invariants
(hypothesis-free), engine greedy determinism across scheduling knobs on
the pinned vocab=32/dh=128/seed-3 workload, and metrics() math against
synthetic timestamps."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.models import lm as lm_mod
from repro.runtime import Runtime, planner
from repro.serving.engine import Request, ServeConfig, ServeEngine
from repro.serving.kv_cache import PagePool

jax.config.update("jax_platform_name", "cpu")

RT = Runtime(impl="ref", q_chunk=16)


# ---------------------------------------------------------------------------
# PagePool: prefix share / revive / evict lifecycle
# ---------------------------------------------------------------------------

def test_pool_prefix_share_revive_evict():
    pool = PagePool(6, 4)
    t = np.arange(8, dtype=np.int32)                  # two full pages
    pages0 = pool.allocate(0, 10)                     # 3 pages
    pool.register_prefix(0, t)
    assert pool.match_prefix(t) == pages0[:2]
    # positional chain: the same block at a different position must miss
    assert pool.match_prefix(t[4:]) == []
    # share while the owner lives: refcount bump, one page counted once
    t2 = np.concatenate([t, [42]]).astype(np.int32)
    shared = pool.match_prefix(t2)
    pages1 = pool.allocate(1, 11, shared_prefix=shared)
    assert pages1[:2] == shared
    assert pool.ref_count(shared[0]) == 2
    assert pool.stats.pages_in_use == 4               # 3 + 1 fresh
    assert pool.stats.prefix_pages_shared == 2
    # first owner releases: shared pages survive on the second owner
    pool.release(0)
    assert pool.ref_count(shared[0]) == 1
    assert pool.free_pages() == 3
    # last owner releases: pages free but stay indexed (lazy eviction)
    pool.release(1)
    assert pool.free_pages() == 6
    assert pool.match_prefix(t) == shared
    # a new request revives the cached pages out of the free list
    pages2 = pool.allocate(2, 9, shared_prefix=pool.match_prefix(t2))
    assert pages2[:2] == shared
    assert pool.ref_count(shared[0]) == 1
    pool.release(2)
    # fresh allocations that reuse the physical pages evict the cache
    assert pool.allocate(3, 24) is not None           # the whole pool
    assert pool.match_prefix(t) == []
    pool.validate()


def test_register_prefix_requires_live_seq_and_is_idempotent():
    pool = PagePool(4, 4)
    with pytest.raises(KeyError, match="not live"):
        pool.register_prefix(9, np.arange(4, dtype=np.int32))
    t = np.arange(8, dtype=np.int32)
    pool.allocate(0, 8)
    pool.register_prefix(0, t)
    before = pool.cached_prefix_pages()
    pool.register_prefix(0, t)                        # no-op, no dup entries
    assert pool.cached_prefix_pages() == before == 2
    # partial feed registers only the full pages covered so far
    pool.allocate(1, 8)
    pool.register_prefix(1, np.arange(100, 108, dtype=np.int32), 5)
    assert pool.match_prefix(np.arange(100, 108, dtype=np.int32)) \
        == [pool.seq_pages(1)[0]]
    pool.validate()


# ---------------------------------------------------------------------------
# Satellite: allocate() atomicity on every failure path
# ---------------------------------------------------------------------------

def _snapshot(pool):
    return (list(pool._free), list(pool._ref),
            {k: list(v) for k, v in pool._seq_pages.items()},
            dict(pool._index), dict(pool._page_key),
            dataclasses.replace(pool.stats))


def test_allocate_failure_leaves_pool_state_untouched():
    """A failing allocate — any raised caller error — must leave the free
    list, refcounts, sequence map, prefix index and stats exactly as they
    were: no leaked or half-reserved pages."""
    pool = PagePool(6, 4)
    t = np.arange(8, dtype=np.int32)
    pool.allocate(0, 10)
    pool.register_prefix(0, t)
    shared = pool.match_prefix(t)
    snap = _snapshot(pool)

    # duplicate seq id
    with pytest.raises(KeyError, match="already allocated"):
        pool.allocate(0, 4)
    assert _snapshot(pool) == snap
    # shared page that is neither live nor indexed (stale match)
    with pytest.raises(ValueError, match="not.*shareable|neither"):
        pool.allocate(1, 12, shared_prefix=[5])
    assert _snapshot(pool) == snap
    # out-of-range and duplicated shared pages
    with pytest.raises(ValueError, match="out of range or duplicated"):
        pool.allocate(1, 12, shared_prefix=[99])
    assert _snapshot(pool) == snap
    with pytest.raises(ValueError, match="out of range or duplicated"):
        pool.allocate(1, 12, shared_prefix=[shared[0], shared[0]])
    assert _snapshot(pool) == snap
    # more shared pages than the reservation needs
    with pytest.raises(ValueError, match="only need"):
        pool.allocate(1, 4, shared_prefix=shared)
    assert _snapshot(pool) == snap
    # capacity denial: returns None, moves ONLY the denial counters
    assert pool.allocate(1, 100) is None
    free, ref, seqs, index, inverse, stats = _snapshot(pool)
    assert (free, ref, seqs, index, inverse) == snap[:5]
    assert stats.pages_in_use == snap[5].pages_in_use
    assert stats.alloc_calls == snap[5].alloc_calls + 1
    assert stats.admission_denials == snap[5].admission_denials + 1
    # the pool still works after every error path
    assert pool.allocate(1, 12, shared_prefix=shared) is not None
    pool.validate()


# ---------------------------------------------------------------------------
# Satellite: seeded randomized pool invariants (hypothesis-free)
# ---------------------------------------------------------------------------

def test_pool_invariants_randomized():
    """Across interleaved allocate/share/release/register sequences:
    free+held page conservation, refcount == number of owning sequences
    (no page in two sequences unless its refcount says so), free-list
    exactness, index consistency, and PoolStats occupancy bounds / peak
    monotonicity. Seeded — failures reproduce."""
    rng = np.random.default_rng(0)
    for n_pages, ps in ((8, 4), (16, 8), (5, 16)):
        pool = PagePool(n_pages, ps)
        live: dict[int, np.ndarray] = {}
        registered: list[np.ndarray] = []
        next_id = 0
        peak_prev = 0
        for _ in range(300):
            op = rng.random()
            if op < 0.5:
                if registered and rng.random() < 0.5:
                    base = registered[int(rng.integers(len(registered)))]
                    tail = rng.integers(0, 100, int(rng.integers(0, 2 * ps)))
                    tokens = np.concatenate([base, tail]).astype(np.int32)
                else:
                    tokens = rng.integers(
                        0, 100, int(rng.integers(1, 4 * ps))).astype(np.int32)
                n_total = len(tokens) + int(rng.integers(1, ps))
                shared = pool.match_prefix(tokens)
                shared = shared[:pool.pages_for(n_total)]
                if len(shared) * ps >= len(tokens):
                    shared = shared[:-1]        # engine's COW cap
                if pool.allocate(next_id, n_total,
                                 shared_prefix=shared) is not None:
                    live[next_id] = tokens
                next_id += 1
            elif op < 0.75 and live:
                sid = int(rng.choice(list(live)))
                pool.register_prefix(sid, live[sid])
                registered.append(live[sid])
            elif live:
                sid = int(rng.choice(list(live)))
                pool.release(sid)
                del live[sid]
            pool.validate()
            # cross-check through the public API too
            owners: dict[int, int] = {}
            for sid in live:
                for p in pool.seq_pages(sid):
                    owners[p] = owners.get(p, 0) + 1
            for p in range(n_pages):
                assert pool.ref_count(p) == owners.get(p, 0)
            assert pool.free_pages() + pool.stats.pages_in_use == n_pages
            assert 0.0 <= pool.stats.occupancy <= 1.0
            assert pool.stats.peak_pages_in_use >= peak_prev
            peak_prev = pool.stats.peak_pages_in_use
        assert pool.stats.alloc_calls > 0 and pool.stats.release_calls > 0
        assert pool.stats.prefix_pages_shared > 0, \
            "randomized driver never exercised sharing"


def test_pool_two_tier_invariants_randomized():
    """Extend the seeded invariant storm to the two-tier (device + host)
    pool: interleaved allocate/offload/onload/release sequences must keep
    byte payloads conserved across tiers (onload returns exactly the
    bytes offload parked), host occupancy == the sum of parked entries,
    the free list exact after every onload, no double offload, and
    validate() green after every op. Seeded — failures reproduce."""
    rng = np.random.default_rng(1)
    for n_pages, ps, host in ((8, 4, None), (16, 8, 6), (6, 4, 3)):
        pool = PagePool(n_pages, ps, host_pages=host)
        live: dict[int, int] = {}           # sid -> n_total tokens
        parked: dict[int, tuple] = {}       # sid -> (n_total, n_keep, bytes)
        next_id = 0
        for _ in range(400):
            op = rng.random()
            if op < 0.35:
                n_total = int(rng.integers(1, 3 * ps))
                if pool.allocate(next_id, n_total) is not None:
                    live[next_id] = n_total
                next_id += 1
            elif op < 0.55 and live:
                sid = int(rng.choice(list(live)))
                n_keep = int(rng.integers(0, pool.seq_page_count(sid) + 1))
                payload = rng.integers(0, 256, 16).astype(np.uint8)
                free_before = pool.free_pages()
                releasable = pool.releasable_pages(sid)
                if pool.offload(sid, n_keep, payload.copy()) is None:
                    # denial only ever means the host bound, and it is
                    # side-effect free
                    assert host is not None
                    assert pool.stats.host_pages_in_use + n_keep > host
                    assert pool.free_pages() == free_before
                else:
                    assert pool.free_pages() == free_before + releasable
                    parked[sid] = (live.pop(sid), n_keep, payload)
                    with pytest.raises(KeyError, match="offload"):
                        pool.offload(sid, 0)        # no double offload
            elif op < 0.75 and parked:
                sid = int(rng.choice(list(parked)))
                n_total, n_keep, payload = parked[sid]
                res = pool.onload(sid, n_total)
                if res is not None:
                    pages, got = res
                    assert np.array_equal(got, payload), \
                        "payload bytes not conserved across tiers"
                    assert len(pages) == pool.pages_for(n_total)
                    live[sid] = n_total
                    del parked[sid]
            elif live:
                sid = int(rng.choice(list(live)))
                pool.release(sid)
                del live[sid]
            pool.validate()
            assert pool.stats.host_pages_in_use == \
                sum(k for _, k, _ in parked.values())
            assert pool.free_pages() + pool.stats.pages_in_use == n_pages
        assert pool.stats.offload_calls > 0 and pool.stats.onload_calls > 0


def test_offload_onload_errors_and_free_list_exactness():
    pool = PagePool(6, 4, host_pages=2)
    with pytest.raises(KeyError, match="not live"):
        pool.offload(0, 1)
    with pytest.raises(KeyError, match="not offloaded"):
        pool.onload(0, 8)
    pages0 = pool.allocate(0, 8)                    # 2 pages
    with pytest.raises(ValueError, match="n_host_pages"):
        pool.offload(0, 3)                          # owns only 2
    assert pool.offload(0, 2, "blob") == 2
    assert pool.host_resident(0) and pool.host_payload_pages(0) == 2
    assert pool.free_pages() == 6
    with pytest.raises(KeyError, match="double offload"):
        pool.offload(0, 1)
    # host tier full: denial, victim stays live
    pool.allocate(1, 8)
    assert pool.offload(1, 2) is None
    assert pool.seq_pages(1) and not pool.host_resident(1)
    # onload restores the payload and the free list exactly
    pages, payload = pool.onload(0, 8)
    assert payload == "blob" and len(pages) == 2
    assert pool.free_pages() == 6 - 2 - 2
    assert not pool.host_resident(0)
    with pytest.raises(KeyError, match="not offloaded"):
        pool.onload(0, 8)
    pool.validate()
    # shared pages survive a co-owner's offload (ref-aware release)
    t = np.arange(4, dtype=np.int32)
    pool.register_prefix(0, t)
    shared = pool.match_prefix(np.concatenate([t, [9]]).astype(np.int32))
    pool.allocate(2, 5, shared_prefix=shared)
    assert pool.ref_count(shared[0]) == 2
    assert pool.offload(0, 2) == 1                  # shared page stays
    assert pool.ref_count(shared[0]) == 1
    pool.validate()
    del pages0


def test_prefix_cache_capacity_lru_eviction():
    """cache_pages bounds the cached-free index: past it, the
    least-recently-touched entry is evicted (and counted); pages pinned
    by live owners never count against the bound."""
    pool = PagePool(8, 4, cache_pages=2)
    prompts = [np.arange(10 * i, 10 * i + 4, dtype=np.int32)
               for i in range(3)]
    for sid, t in enumerate(prompts):
        pool.allocate(sid, 4)
        pool.register_prefix(sid, t)
    # three live indexed pages: fine, the bound counts cached-FREE only
    assert pool.cached_prefix_pages() == 3
    pool.validate()
    pool.release(0)
    pool.release(1)
    assert pool.stats.prefix_evictions == 0
    pool.release(2)                     # third cached-free page: evict LRU
    assert pool.stats.prefix_evictions == 1
    assert pool.match_prefix(prompts[0]) == []      # oldest touch evicted
    assert len(pool.match_prefix(prompts[1])) == 1
    assert len(pool.match_prefix(prompts[2])) == 1
    pool.validate()
    # the matches above touched prompts[1] then prompts[2]: registering a
    # third cached-free entry evicts prompts[1], the oldest touch
    pool.allocate(3, 4)
    pool.register_prefix(3, prompts[0])
    pool.release(3)
    assert pool.stats.prefix_evictions == 2
    assert pool.match_prefix(prompts[1]) == []      # oldest touch evicted
    assert len(pool.match_prefix(prompts[2])) == 1
    pool.validate()


def test_fresh_allocations_prefer_unindexed_pages():
    """A cached prefix must be the LAST thing a fresh allocation
    recycles: free un-indexed pages go first."""
    pool = PagePool(4, 4)
    t = np.arange(4, dtype=np.int32)
    pool.allocate(0, 4)
    pool.register_prefix(0, t)
    cached = pool.seq_pages(0)[0]
    pool.release(0)                     # cached-free now
    pages = pool.allocate(1, 12)        # 3 of 4 pages fresh
    assert cached not in pages, "fresh alloc recycled the cached prefix"
    assert len(pool.match_prefix(t)) == 1
    # only when every free page is indexed does the LRU one recycle
    pages2 = pool.allocate(2, 4)
    assert pages2 == [cached]
    assert pool.match_prefix(t) == []   # evicted with the reuse
    assert pool.stats.prefix_evictions >= 1
    pool.validate()


def test_prefix_lookup_hit_counters():
    pool = PagePool(4, 4)
    t = np.arange(8, dtype=np.int32)
    pool.allocate(0, 8)
    assert pool.match_prefix(t) == []               # miss
    pool.register_prefix(0, t)
    assert len(pool.match_prefix(t)) == 2           # hit
    assert pool.stats.prefix_lookups == 2
    assert pool.stats.prefix_hits == 1
    pool.validate()


def test_plan_seq_pages_model():
    assert planner.plan_seq_pages(33, 8) == 5
    assert planner.plan_seq_pages(33, 8, shared_tokens=24) == 2
    # COW case: a partially reused last page still bills as fresh
    assert planner.plan_seq_pages(32, 8, shared_tokens=31) == 1
    assert planner.plan_seq_pages(0, 8) == 0
    with pytest.raises(ValueError):
        planner.plan_seq_pages(8, 8, shared_tokens=9)
    with pytest.raises(ValueError):
        planner.plan_seq_pages(8, 0)


# ---------------------------------------------------------------------------
# Satellite: engine greedy determinism on the pinned workload
# ---------------------------------------------------------------------------

# vocab=32 keeps top-2 logit gaps wide relative to the quantization error
# (exact-output asserts at vocab=512 flip on near-ties); dh=128 keeps the
# quantized byte ratios representative — same pinned workload as
# benchmarks/serving_bench.py.
CFG_PIN = dataclasses.replace(reduced(get_config("gemma-2b"), vocab=32),
                              head_dim=128)


def _drive(params, rt, prompts, order, slots, prefix_on):
    eng = ServeEngine(params, CFG_PIN,
                      ServeConfig(batch_slots=slots, max_seq=48,
                                  quantize="sp2_4", kv_layout="paged",
                                  page_size=8, prefix_cache=prefix_on),
                      rt=rt)
    for i in order:
        eng.submit(Request(rid=i, prompt=prompts[i], max_new_tokens=3))
    out = {r.rid: r.output for r in eng.run()}
    eng.pool.validate()
    return out


@pytest.mark.parametrize("kvq", [False, True], ids=["f32", "kv_quant"])
def test_engine_greedy_invariant_to_schedule_knobs(kvq):
    """Greedy outputs on the pinned seed-3 workload are a function of
    (params, prompt) only: invariant to request submit order, batch_slots,
    and prefix-cache on/off — for plain paged and paged+kv_quant pools."""
    rt = RT.replace(kv_quant=True, kv_scheme="spx_8_x3") if kvq else RT
    params = lm_mod.lm_init(jax.random.PRNGKey(3), CFG_PIN)
    rng = np.random.default_rng(3)
    sys_p = rng.integers(0, CFG_PIN.vocab_size, 8).astype(np.int32)
    # one bare page-aligned duplicate (index 2) so the COW path is in play
    prompts = [np.concatenate(
        [sys_p, rng.integers(0, CFG_PIN.vocab_size, n).astype(np.int32)])
        for n in (2, 5, 0, 9)]

    base = _drive(params, rt, prompts, [0, 1, 2, 3], 2, False)
    assert _drive(params, rt, prompts, [3, 1, 0, 2], 2, False) == base
    assert _drive(params, rt, prompts, [0, 1, 2, 3], 3, False) == base
    assert _drive(params, rt, prompts, [0, 1, 2, 3], 2, True) == base
    assert _drive(params, rt, prompts, [2, 3, 0, 1], 3, True) == base


# ---------------------------------------------------------------------------
# Satellite: metrics() math on synthetic timestamps
# ---------------------------------------------------------------------------

def _mini_engine(**kw):
    cfg = reduced(get_config("granite-3-8b"))
    params = lm_mod.lm_init(jax.random.PRNGKey(0), cfg)
    return ServeEngine(params, cfg,
                       ServeConfig(batch_slots=2, max_seq=16, quantize=None,
                                   **kw),
                       rt=RT)


def _fake_request(rid, enq, ttft_s, lat_s):
    r = Request(rid=rid, prompt=np.zeros(2, np.int32), max_new_tokens=4)
    r.t_enqueue = enq
    r.t_admitted = enq + ttft_s / 2
    r.t_first_token = enq + ttft_s
    r.t_done = enq + lat_s
    r.done = True
    return r


def test_metrics_math_synthetic_timestamps():
    eng = _mini_engine(kv_layout="dense")
    ttfts = (0.010, 0.020, 0.030, 0.040)
    lats = (0.100, 0.200, 0.300, 0.400)
    eng.finished = [_fake_request(i, 50.0 * i, t, l)
                    for i, (t, l) in enumerate(zip(ttfts, lats))]
    eng._tokens_out = 40
    eng._wall = 2.0
    eng._steps = 7
    m = eng.metrics()
    assert m["tokens_per_s"] == 20.0
    assert m["requests_finished"] == 4 and m["engine_steps"] == 7
    assert m["ttft_p50_ms"] == pytest.approx(25.0)
    # linear-interpolated p95 of [10, 20, 30, 40] ms: 30 + 0.85*10
    assert m["ttft_p95_ms"] == pytest.approx(38.5)
    assert m["latency_p50_ms"] == pytest.approx(250.0)
    assert m["latency_p95_ms"] == pytest.approx(385.0)
    # queue waits of [5, 10, 15, 20] ms (admitted halfway to the first
    # token)
    assert m["queue_wait_p50_ms"] == pytest.approx(12.5)
    assert m["queue_wait_p95_ms"] == pytest.approx(19.25)


def test_metrics_single_sample_p95_equals_the_sample():
    eng = _mini_engine(kv_layout="dense")
    eng.finished = [_fake_request(0, 5.0, 0.007, 0.050)]
    m = eng.metrics()
    assert m["ttft_p50_ms"] == m["ttft_p95_ms"] == pytest.approx(7.0)
    assert m["latency_p50_ms"] == m["latency_p95_ms"] == pytest.approx(50.0)


def test_reset_metrics_clears_counters_and_prefix_stats():
    eng = _mini_engine(kv_layout="paged", page_size=8, prefix_cache=True)
    eng.finished = [_fake_request(0, 1.0, 0.001, 0.002)]
    eng._tokens_out, eng._wall, eng._steps = 10, 1.0, 3
    eng._occ_sum, eng._occ_n, eng._occ_peak = 0.5, 1, 0.5
    eng._launches.update(prefill=2, decode=3)
    eng._prefill_valid, eng._prefill_computed = 5, 8
    eng._prefix_hits, eng._prefill_skipped, eng._cow_copies = 3, 42, 2
    eng.pool.stats.admission_denials = 5
    eng.reset_metrics()
    m = eng.metrics()
    assert m["requests_finished"] == 0 and m["tokens_generated"] == 0
    assert m["wall_s"] == 0.0 and m["tokens_per_s"] == 0.0
    assert m["ttft_p50_ms"] == m["ttft_p95_ms"] == 0.0
    assert m["latency_p50_ms"] == m["latency_p95_ms"] == 0.0
    assert m["occupancy_mean"] == m["occupancy_peak"] == 0.0
    assert m["launches"] == {} and m["model_calls"] == 0
    assert m["prefill_row_util"] == 0.0
    assert m["prefix_hits"] == 0 and m["prefill_tokens_skipped"] == 0
    assert m["cow_copies"] == 0 and m["admission_denials"] == 0
    assert m["prefix_cache"] is True
