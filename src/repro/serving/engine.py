"""Batched serving engine over the compiled step functions, with
SPx-quantized weights (the paper's deployment mode). Single-host execution
here; the distributed dry-run exercises the same step functions on the
production meshes.

Two KV layouts (docs/SERVING.md has the full lifecycle):

* **paged** (the default for every architecture): serving state lives in
  a unified **StateCache** (serving/kv_cache.py) with three regions under
  one budget — token-paged KV for attn/xdec mixers, fixed-size **slabs**
  of recurrent state for SSM mixers (mamba/mlstm/slstm — one slab per
  live sequence covering every SSM slot x period), and a read-only
  shared **cross** region holding encoder-output K/V keyed by a frames
  hash (enc-dec: repeated inputs reuse the whole encoder pass). The
  layer pattern is the routing unit: jamba's attention layers page while
  its mamba layers slab; pure-SSM patterns run pageless. Admission is
  all-or-nothing across regions — a request is admitted when the cache
  covers its worst-case footprint, otherwise it waits in the queue.
  Prompts stream through **chunked prefill** (planner/env-sized chunks,
  one chunk per engine tick per slot, interleaved with decode steps of
  already-running sequences), and every decode tick is one launch of the
  decode program, whose attention is the ragged decode kernel reading
  the pages through the block table. Memory scales with tokens +
  sequences in flight, not ``batch_slots x max_seq``.

* **dense**: the original per-slot ``(B, Hkv, max_seq, dh)`` cache (plus
  per-slot recurrent state / cross-KV blocks where the pattern has
  them); prompts pad to the slot length at admission and decode runs in
  lockstep. Kept as the differential-test baseline for every
  architecture.

The paged layout optionally shares KV pages across requests
(``prefix_cache=True`` / ``--prefix-cache`` / ``REPRO_PREFIX_CACHE=1``):
admission matches the prompt against the pool's prefix index, maps the
matched full pages into the sequence's page list (refcount bump, zero
prefill work), chunk-prefills only the unmatched tail, and copy-on-writes
the final matched page when the whole prompt is page-aligned-identical
(the last prompt token must be re-run for logits and would otherwise
write into a shared page). Greedy outputs are identical with sharing on
or off (regression-tested) — sharing changes where bytes live, never
what they hold.

The paged layout also supports **speculative decoding**
(``spec_decode=True`` / ``--spec-decode`` / ``REPRO_SPEC_K=N``): a
model-free prompt-lookup drafter (serving/spec.py) proposes up to K
tokens per decoding slot from the sequence's own n-gram history, one
decode tick scores the whole K+1 window against the paged cache
(``models/lm.lm_paged_fused_step``), and the engine keeps the longest
accepted prefix plus one bonus/correction token — rolling the KV write
cursor back past any rejected tail. Greedy outputs are identical with
speculation on or off (regression-tested); what changes is model calls
per emitted token (``metrics()["model_calls"]``,
``accepted_per_step``, ``draft_acceptance_rate``).

Either layout composes with the quantized KV cache (``rt.kv_quant`` +
``rt.kv_scheme`` — uniform8 baseline or non-uniform SPx): paged pools
store uint8 codes + per-token scale and decode through the fused-dequant
ragged decode kernel; page/pool byte accounting follows the layout
actually allocated (``kv_cache_dtype``, or codes+scale when quantized).

Both layouts produce identical greedy outputs (regression-tested); the
engine exposes throughput/occupancy metrics either way via ``metrics()``.

Sampling (``temperature > 0``) draws from a per-request PRNG chain
(``Request.seed``, default derived from the engine seed and the rid), so
a sampled request's output is a function of the request alone — not of
submit order or which other requests share the batch.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.launch.mesh import make_serving_mesh
from repro.models import encdec as encdec_mod
from repro.models import lm as lm_mod
from repro.nn.layers import quantize_params
from repro.runtime import Runtime, planner
from repro.serving.config import (ServeConfig, _decode_pattern_cfg,
                                  _slab_mixers)
from repro.serving.kv_cache import (StateCache, cross_kv_bytes_per_seq,
                                    kv_bytes_per_token, page_bytes,
                                    ssm_state_bytes_per_seq)
from repro.serving.spans import span
from repro.serving.spec import PromptLookupDrafter
from repro.serving.stream import StreamState, TokenStream
from repro.sharding import ShardingPolicy

__all__ = ["Request", "ServeConfig", "ServeEngine"]

#: every engine timestamp (t_enqueue / t_first_token / t_done, wall
#: accounting) comes through this hook. It must be a *monotonic* clock:
#: TTFT and latency are differences of these stamps, and wall-clock
#: ``time.time()`` can step backwards under NTP adjustment, turning a
#: latency percentile negative. Module-level so the fake-clock
#: regression test can monkeypatch it.
_now = time.monotonic


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray              # (len,) int32
    max_new_tokens: int = 32
    temperature: float = 0.0
    #: sampling seed (temperature > 0). None derives a key from the
    #: engine seed and the rid, so two engines with the same seed agree;
    #: either way every draw comes from this request's own key chain —
    #: sampled outputs cannot depend on submit order or batch-mates.
    seed: Optional[int] = None
    #: SLA class for the continuous-batching scheduler: higher values
    #: admit first and may preempt strictly-lower-priority residents;
    #: ties break FIFO by submit order. The FIFO scheduler ignores it.
    priority: int = 0
    #: encoder input for enc-dec models: (S_enc, D) frame embeddings
    #: (the audio conv frontend is stubbed upstream). Required when
    #: cfg.enc_dec; identical frames across requests share one encoded
    #: cross-KV entry in the state cache's cross region.
    frames: Optional[np.ndarray] = None
    # filled by the engine:
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    t_enqueue: float = 0.0
    #: when the scheduler first placed the request in a slot
    t_admitted: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    preemptions: int = 0            # times this request was preempted
    key: object = dataclasses.field(default=None, repr=False)
    # scheduler internals: submit-order tiebreak, and the (write cursor,
    # prefill progress) pair a preempted request resumes from
    _seq: int = dataclasses.field(default=0, repr=False)
    _resume: object = dataclasses.field(default=None, repr=False)


def _pad_pow2(n: int, cap: int) -> int:
    """Smallest power of two >= n, capped (chunk-padding bucket)."""
    return min(cap, 1 << max(0, (n - 1)).bit_length())


class ServeEngine:
    def __init__(self, params, cfg: ArchConfig,
                 config: ServeConfig | None = None, *,
                 rt: Runtime | None = None, devices=None):
        if config is not None and not isinstance(config, ServeConfig):
            raise TypeError(
                f"config must be a ServeConfig, got {type(config).__name__}")
        self.cfg = cfg
        self.rt = rt or Runtime(impl="auto", q_chunk=256)
        # ALL env fallback + cross-knob validation happens here, nowhere
        # else in the engine (docs/SERVING.md "ServeConfig")
        sc = (config or ServeConfig()).resolve(cfg)
        self.config = sc
        if sc.replicas > 1:
            raise ValueError(
                f"replicas={sc.replicas} is a router knob — build a "
                "repro.serving.ReplicaRouter for data-parallel replicas "
                "(a bare ServeEngine is always one replica)")
        self.batch_slots = sc.batch_slots
        self.max_seq = sc.max_seq
        self.kv_cache_dtype = jnp.dtype(sc.kv_cache_dtype)
        self.kv_layout = sc.kv_layout
        self.prefix_cache = sc.prefix_cache
        self.spec_k = sc.spec_k
        self.scheduler = sc.scheduler
        self.host_pages = sc.host_pages
        self.prefix_cache_pages = sc.prefix_cache_pages
        self.shards = sc.shards
        # KV quantization (scheme-parameterized, docs/QUANTIZATION.md):
        # whenever rt.kv_quant is set the cache layout is uint8 codes +
        # f32 scale and kv_cache_dtype is IGNORED by the cache allocators
        # (metrics() then reports the layout, not the dtype arg)
        self.kv_scheme = self.rt.kv_scheme if self.rt.kv_quant else None
        if sc.quantize:
            params = quantize_params(params, sc.quantize)
        self.params = params
        # base for per-request sampling keys (Request.seed overrides)
        self._base_key = jax.random.PRNGKey(sc.seed)

        # layer pattern is the routing unit for the unified state cache:
        # attn/xdec mixers page token KV, SSM mixers (mamba/mlstm/slstm)
        # pin one fixed-size slab per live sequence, enc-dec adds a
        # read-only shared cross region. For enc-dec models the DECODER
        # pattern is what holds serving state.
        self._decode_cfg = _decode_pattern_cfg(cfg)
        self._has_pages = any(s.split("+")[0] in ("attn", "xdec")
                              for s in self._decode_cfg.pattern)
        self._has_slab = bool(_slab_mixers(self._decode_cfg))
        self._has_cross = bool(cfg.enc_dec)

        # tensor parallelism (shards > 1): build a (data=1, model=shards)
        # mesh over an explicit device slice, place params by the same
        # ShardingPolicy the dry-run uses (Megatron TP), head-shard the
        # paged pools, and thread the mesh through Runtime so the forward
        # passes plant their sharding constraints. GSPMD partitions the
        # jitted steps; block tables and token batches stay replicated.
        self.mesh = None
        self._policy = None
        #: the one device a single-shard engine was pinned to (None: the
        #: default device); state caches are allocated there too
        self.device = None
        if sc.shards > 1:
            devs = (list(devices) if devices is not None
                    else list(jax.devices()))
            if len(devs) < sc.shards:
                raise ValueError(
                    f"shards={sc.shards} needs at least {sc.shards} "
                    f"devices, have {len(devs)} — on CPU set "
                    "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                    "before jax initializes (repro.launch.hostdev)")
            self.mesh = make_serving_mesh(model=sc.shards,
                                          devices=devs[:sc.shards])
            self.rt = self.rt.replace(mesh=self.mesh, model_axis="model",
                                      data_axes=("data",))
            self._policy = ShardingPolicy(cfg, self.mesh, fsdp=False,
                                          parallelism="tp")
            self.params = jax.device_put(
                self.params,
                self._policy.named(self._policy.param_specs(self.params)))
        elif devices is not None:
            self.device = list(devices)[0]
            self.params = jax.device_put(self.params, self.device)

        self.slot_req: list[Optional[Request]] = [None] * self.batch_slots
        self.slot_pos = np.zeros(self.batch_slots, np.int64)  # tokens held
        self.queue: list[Request] = []
        self.finished: list[Request] = []
        # per-rid delivery state (serving/stream.py): created at submit,
        # terminal at finish/cancel/error; stream() hands out views
        self._streams: dict[int, StreamState] = {}
        self._cancelled = 0
        # per-tick pool (paged) or slot (dense) occupancy
        self._occ_sum = 0.0
        self._occ_n = 0
        self._occ_peak = 0.0
        self._tokens_out = 0
        self._steps = 0
        self._wall = 0.0
        # jitted model programs launched, by program (_launch): their sum
        # is the model-call count speculation shrinks per emitted token
        self._launches: collections.Counter = collections.Counter()
        # prompt tokens, and rows computed, over the prefill launches
        self._prefill_valid = 0
        self._prefill_computed = 0
        # slabs zeroed for fresh admissions
        self._slab_resets = 0
        # speculation counters: per-row windows that carried >= 1 draft
        # (a batched verify call holds one window per drafted slot),
        # and drafts proposed/accepted across them
        self._spec_windows = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        # scheduler counters: preempt/resume traffic and undrained runs
        self._preemptions = 0
        self._resumes = 0
        self._offload_bytes = 0
        self._onload_bytes = 0
        self._undrained_runs = 0
        self._submit_seq = 0
        #: did the last run() drain every request? (satellite of the
        #: old silent-truncation bug: stopping at max_steps with live
        #: work now raises under strict=True and flips this flag)
        self.drained = True

        with jax.default_device(self.device):
            if sc.kv_layout == "paged":
                self._init_paged()
            else:
                self._init_dense()
        if self.mesh is not None:
            # head-shard the paged KV/cross pools over the model axis
            # (replicated where Hkv doesn't divide); slabs replicate.
            # Committing the initial placement is enough — the donated
            # cache argument keeps whatever sharding GSPMD settles on.
            specs = self._policy.paged_state_specs(self.caches)
            self.caches = jax.device_put(self.caches,
                                         self._policy.named(specs))

    def _paged_layers(self) -> int:
        """Layer-slot count of the token-KV page pools (the pools'
        leading dim — what one page spans byte-wise)."""
        n = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.caches):
            keys = [str(getattr(k, "key", getattr(k, "name", "")))
                    for k in path]
            if "kp" in keys:
                n = max(n, int(leaf.shape[0]))
        return max(n, 1)

    # -- layout-specific setup ----------------------------------------------

    def _init_dense(self):
        # cfg and rt are frozen/hashable and ride as *static* jit arguments:
        # an engine whose Runtime is replaced by an equal-valued copy reuses
        # the compiled steps (no retrace — tests/test_runtime.py)
        if self.cfg.enc_dec:
            self._decode = jax.jit(encdec_mod.encdec_decode_step,
                                   static_argnums=(4, 5),
                                   donate_argnums=(3,))
            # encoder + decoder-prompt prefill, one request at a time;
            # frames ride as the extra leading array argument
            self._prefill_one = jax.jit(encdec_mod.encdec_prefill,
                                        static_argnums=(4, 5))
            self.caches = encdec_mod.encdec_init_caches(
                self.cfg, self.batch_slots, self.max_seq,
                dtype=self.kv_cache_dtype, kv_quant=self.rt.kv_quant)
            return
        self._decode = jax.jit(lm_mod.lm_decode_step, static_argnums=(4, 5),
                               donate_argnums=(3,))
        # per-slot position prefill: tokens padded to max_prompt, true
        # lengths masked; logits of the last real token are picked host-side
        self._prefill_one = jax.jit(lm_mod.lm_prefill,
                                    static_argnums=(3, 4))
        self.caches = lm_mod.init_caches(self.cfg, self.batch_slots,
                                         self.max_seq,
                                         dtype=self.kv_cache_dtype,
                                         kv_quant=self.rt.kv_quant)

    def _init_paged(self):
        cfg = self.cfg
        dcfg = self._decode_cfg
        sc = self.config
        if self._has_pages:
            rep = dcfg.n_heads // dcfg.n_kv_heads
            plan = planner.plan_kv_pages(
                dcfg.n_kv_heads, dcfg.dh, rep=rep,
                act_bytes=self.kv_cache_dtype.itemsize,
                kv_scheme=self.kv_scheme)
            self.page_size = min(sc.page_size or plan.page_size,
                                 self.max_seq)
            self.pages_per_seq = -(-self.max_seq // self.page_size)
            # default pool = the dense engine's worst case, so
            # paged-vs-dense comparisons start from equal budgets; pass a
            # smaller pool to get admission backpressure
            # (tests/test_serving.py exercises this)
            n_pages = sc.pool_pages or self.batch_slots * self.pages_per_seq
        else:
            # pageless (pure-SSM pattern): no mixer writes token KV, the
            # pool degenerates to the slab region only
            self.page_size = 1
            self.pages_per_seq = 0
            n_pages = 0
        # one slab per live sequence covers every SSM slot x period; one
        # cross entry per live *distinct input* (shared across sequences)
        self._n_slabs = self.batch_slots if self._has_slab else 0
        self._n_cross = self.batch_slots if self._has_cross else 0
        self.pool = StateCache(n_pages, self.page_size,
                               n_slabs=self._n_slabs,
                               n_cross=self._n_cross,
                               host_pages=self.host_pages,
                               cache_pages=self.prefix_cache_pages)
        # env fallback + validation happened in ServeConfig.resolve()
        self.prefill_chunk = sc.prefill_chunk
        if cfg.enc_dec:
            self.caches = encdec_mod.encdec_paged_init_caches(
                cfg, self.pool.n_pages, self.page_size,
                dtype=self.kv_cache_dtype, kv_quant=self.rt.kv_quant,
                n_slabs=self._n_slabs, n_cross=self._n_cross)
            step_fn = encdec_mod.encdec_paged_step
            fused_fn = encdec_mod.encdec_paged_fused_step
        else:
            self.caches = lm_mod.paged_init_caches(
                cfg, self.pool.n_pages, self.page_size,
                dtype=self.kv_cache_dtype, kv_quant=self.rt.kv_quant,
                n_slabs=self._n_slabs, n_cross=self._n_cross)
            step_fn = lm_mod.lm_paged_step
            fused_fn = lm_mod.lm_paged_fused_step
        self._paged_step = jax.jit(step_fn, static_argnums=(7, 8),
                                   donate_argnums=(6,))
        # the decode tick: one compiled function over a fixed window per
        # row — 1, or spec_k + 1 with speculation — in which every layer's
        # attention is one paged_decode_ragged launch
        self._fused_step = jax.jit(fused_fn, static_argnums=(7, 8),
                                   donate_argnums=(6,))
        if self.spec_k:
            self.drafter = PromptLookupDrafter()
        # copy-on-write page duplication; src/dst ride as traced scalars
        # so the one compile covers every page pair
        self._copy_page = jax.jit(lm_mod.paged_copy_page,
                                  donate_argnums=(0,))
        # preemption snapshot/restore: whole-page gather to host and
        # scatter back. Page-index vectors are traced and pow2-padded, so
        # O(log pages_per_seq) compiles cover every preemption shape.
        self._gather_pages = jax.jit(lm_mod.paged_gather_pages)
        self._scatter_pages = jax.jit(lm_mod.paged_scatter_pages,
                                      donate_argnums=(0,))
        if self._has_slab:
            # slab snapshot/restore (preemption) and the fresh-admission
            # zero; the slab index rides as a traced scalar
            self._gather_slabs = jax.jit(lm_mod.paged_gather_slabs)
            self._scatter_slabs = jax.jit(lm_mod.paged_scatter_slabs,
                                          donate_argnums=(0,))
            self._reset_slabs = jax.jit(lm_mod.paged_reset_slabs,
                                        donate_argnums=(0,))
        if self._has_cross:
            # encoder pass + per-slot cross-KV projection, run once per
            # DISTINCT frames (the cross region shares entries by key)
            self._encode_cross = jax.jit(encdec_mod.encdec_cross_kv,
                                         static_argnums=(2, 3))
            self._fill_cross = jax.jit(lm_mod.paged_fill_cross,
                                       donate_argnums=(0,))
        # per-slot (slab, cross) indices for the step functions;
        # out-of-range sentinels mean "no slab / no cross entry"
        self._state_idx = np.tile(
            np.array([self._n_slabs, self._n_cross], np.int32),
            (self.batch_slots, 1))
        self.block_tables = np.zeros(
            (self.batch_slots, max(self.pages_per_seq, 1)), np.int32)
        # per-slot prefill progress: tokens of the prompt already fed;
        # -1 means the slot is decoding
        self._fed = np.full(self.batch_slots, -1, np.int64)
        # frames hash per in-flight rid (cross-region key), computed once
        self._frames_keys: dict[int, bytes] = {}
        # prefix-cache work counters (metrics(); reset_metrics() zeroes)
        self._prefix_hits = 0
        self._prefill_skipped = 0
        self._cow_copies = 0
        # per-request chain keys, hashed once at first admission attempt
        # and reused by every retry tick and prefill-chunk registration
        self._prompt_keys: dict[int, list[bytes]] = {}

    # -- public API ----------------------------------------------------------

    @staticmethod
    def _worst_case_tokens(req: Request) -> int:
        """Tokens the sequence can ever hold — admission reserves this."""
        return len(req.prompt) + req.max_new_tokens

    def _frames_key(self, req: Request) -> bytes | None:
        """Content hash of the request's frames — the cross-region key.
        Identical frames hash equal, so concurrent requests decoding the
        same input share one encoded entry. Cached per rid: admission
        retries must not re-hash 1500-frame inputs every tick."""
        if not self._has_cross:
            return None
        key = self._frames_keys.get(req.rid)
        if key is None:
            f = np.ascontiguousarray(req.frames, np.float32)
            h = hashlib.blake2b(f.tobytes(), digest_size=16)
            h.update(repr(f.shape).encode())
            key = self._frames_keys[req.rid] = h.digest()
        return key

    def _sync_state_idx(self, slot: int, rid: int):
        """Point the slot's (slab, cross) row at the pool's current
        assignment (sentinels where the pattern has no such region)."""
        slab = self.pool.seq_slab(rid)
        cross = self.pool.seq_cross(rid)
        self._state_idx[slot, 0] = self._n_slabs if slab is None else slab
        self._state_idx[slot, 1] = (self._n_cross if cross is None
                                    else cross)

    def _set_block_row(self, slot: int, rid: int):
        if self.pages_per_seq:
            self.block_tables[slot] = self.pool.block_table_row(
                rid, self.pages_per_seq)

    def submit(self, req: Request):
        with span("serve.submit"):
            self._enqueue(req)

    def _enqueue(self, req: Request):
        if len(req.prompt) < 1 or req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: needs a non-empty prompt and "
                f"max_new_tokens >= 1 (got {len(req.prompt)}, "
                f"{req.max_new_tokens})")
        if self.cfg.enc_dec and req.frames is None:
            raise ValueError(
                f"request {req.rid}: {self.cfg.name} is enc-dec — every "
                "request needs frames=(S_enc, D) encoder input")
        if not self.cfg.enc_dec and req.frames is not None:
            raise ValueError(
                f"request {req.rid}: frames= given but {self.cfg.name} "
                "has no encoder")
        if self._worst_case_tokens(req) > self.max_seq:
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.max_new_tokens}) exceeds "
                f"max_seq={self.max_seq}")
        if req.done or req.output:
            # a served Request object is not reusable: its PRNG key chain
            # has advanced past every draw it made, and t_first_token /
            # t_done / preemptions hold the previous run's values —
            # resubmitting it would silently produce a different sampled
            # output and corrupt every latency percentile
            raise ValueError(
                f"request {req.rid}: this Request object was already "
                f"served ({len(req.output)} output token(s), "
                f"done={req.done}) — build a fresh Request per "
                "submission")
        in_flight = ({r.rid for r in self.queue}
                     | {r.rid for r in self.slot_req if r is not None})
        if req.rid in in_flight:
            # rids key the page allocator AND every consumer's results
            # dict; a duplicate would KeyError mid-run (paged) or
            # silently overwrite another request's output (dense)
            raise ValueError(f"request id {req.rid} already in flight")
        if any(r.rid == req.rid for r in self.finished):
            # same key-collision hazard one step later: finished results
            # and stream states are looked up by rid. reset_metrics()
            # clears `finished`, so the benchmark warmup-then-measure
            # pattern stays legal with fresh Request objects.
            raise ValueError(
                f"request id {req.rid} already finished this measurement "
                "window — reuse a rid only after reset_metrics(), and "
                "always with a fresh Request object")
        if self.kv_layout == "paged" and self._has_pages:
            # worst-case reservation (planner-owned model): assume no
            # shared prefix — the index is volatile, so a match visible
            # now may be evicted before this request reaches admission
            need = planner.plan_seq_pages(self._worst_case_tokens(req),
                                          self.page_size)
            if need > self.pool.n_pages:
                # could never be admitted even against an empty pool —
                # reject now instead of busy-spinning run() forever
                raise ValueError(
                    f"request {req.rid}: needs {need} pages but the pool "
                    f"only has {self.pool.n_pages} in total")
        if req.key is None:
            # per-request chain: explicit seed wins; otherwise derive from
            # the engine seed + rid (stable across batch compositions)
            req.key = (jax.random.PRNGKey(req.seed)
                       if req.seed is not None
                       else jax.random.fold_in(self._base_key, req.rid))
        req.t_enqueue = _now()
        req._seq = self._submit_seq
        self._submit_seq += 1
        self._streams[req.rid] = StreamState(req)
        self.queue.append(req)

    def step(self):
        """One public scheduling tick: admission (with preemption under
        the cb scheduler), a prefill chunk per prefilling slot, one decode
        tick. Callers that interleave ``submit`` with engine progress —
        arrival processes in benchmarks, the differential storm tests —
        drive this directly; ``run`` is this in a drain loop."""
        t0 = _now()
        self._tick()
        self._wall += _now() - t0

    def has_work(self) -> bool:
        """Anything queued or resident? The asyncio front-end's
        tick-or-idle signal (and run()'s drain condition)."""
        return bool(self.queue) or any(r is not None
                                       for r in self.slot_req)

    def _tick(self):
        self._steps += 1
        paged = self.kv_layout == "paged"
        live = [i for i, r in enumerate(self.slot_req) if r is not None]
        prefilling = sum(self._fed[i] >= 0 for i in live) if paged else 0
        with span("serve.tick", queued=len(self.queue),
                  prefilling=int(prefilling),
                  decoding=len(live) - int(prefilling)):
            with span("serve.admit") as s:
                queued, preempted = len(self.queue), self._preemptions
                if paged:
                    self._admit_paged()
                else:
                    self._admit_dense()
                preempted = self._preemptions - preempted
                s.set_metadata(
                    admitted=queued + preempted - len(self.queue),
                    preempted=preempted)
            if paged:
                self._prefill_tick()
                self._decode_step_paged()
                occ = self.pool.stats.occupancy
            else:
                self._decode_step_dense()
                occ = (sum(r is not None for r in self.slot_req)
                       / self.batch_slots)
            self._occ_sum += occ
            self._occ_n += 1
            self._occ_peak = max(self._occ_peak, occ)
            # wake async stream consumers once per tick — every emission
            # of this tick is already in Request.output by now
            for st in self._streams.values():
                st.notify()

    def run(self, max_steps: int = 10_000, *, strict: bool = True):
        """Drive until queue + slots drain (or step limit).

        Hitting ``max_steps`` with live requests used to return silently,
        dropping queued/resident work on the floor. Now it surfaces:
        ``self.drained`` flips False, the ``undrained_runs`` metric
        increments, and under ``strict=True`` (the default) a
        RuntimeError is raised — pass ``strict=False`` to accept the
        partial ``finished`` list instead."""
        t0 = _now()
        for _ in range(max_steps):
            if not self.has_work():
                break
            self._tick()
        self._wall += _now() - t0
        self.drained = not self.has_work()
        if not self.drained:
            self._undrained_runs += 1
            if strict:
                exc = RuntimeError(
                    f"run(max_steps={max_steps}) stopped with live work: "
                    f"{len(self.queue)} queued, "
                    f"{sum(r is not None for r in self.slot_req)} resident "
                    f"({len(self.finished)} finished). Raise max_steps, or "
                    f"pass strict=False to accept partial progress.")
                # streams of the still-live requests get a terminal error
                # state (not a silent hang): pending consumers raise
                # StreamError instead of waiting for tokens that will
                # never come
                self._fail_streams(exc)
                raise exc
        return self.finished

    # -- incremental delivery + cancellation (serving/stream.py) -------------

    def stream(self, rid: int) -> TokenStream:
        """A token iterator over one submitted request. Sync iteration
        drives ``step()`` itself when it runs dry; ``async for`` parks on
        a per-tick wakeup instead (an external loop must tick the
        engine). Every stream sees the full output exactly once — tokens
        are read from ``Request.output`` behind a cursor, so delivery is
        bit-identical to the ``run()`` result by construction. Raises
        KeyError for a rid this engine never saw (or whose terminal
        stream state ``reset_metrics`` already pruned)."""
        st = self._streams.get(rid)
        if st is None:
            raise KeyError(
                f"request {rid}: no stream state (never submitted, or "
                "pruned by reset_metrics())")
        return TokenStream(self, st)

    def cancel(self, rid: int) -> bool:
        """Abort a request mid-flight at any tick boundary and free
        everything it holds — pages/slabs (ref-aware), drafter state,
        prompt/frames keys, and for a preempted-and-parked request its
        host-tier snapshot plus the cross reference offload retained.
        Open streams turn terminal (``StreamCancelled``); the request
        never joins ``finished``. Returns True when live work was
        cancelled, False when the request already reached a terminal
        state (finished, or cancelled before). Raises KeyError for a rid
        this engine never saw."""
        st = self._streams.get(rid)
        for slot, r in enumerate(self.slot_req):
            if r is not None and r.rid == rid:
                # resident: queued-for-decode, mid-prefill, mid-verify —
                # all hold the same reservation; release() recycles
                # zero-ref pages, the slab, and the cross reference
                self.slot_req[slot] = None
                self.slot_pos[slot] = 0
                if self.kv_layout == "paged":
                    self.pool.release(rid)
                    self.block_tables[slot] = 0
                    self._fed[slot] = -1
                    self._state_idx[slot] = (self._n_slabs, self._n_cross)
                    if self.spec_k:
                        self.drafter.drop(rid)
                self._drop_request(r, st)
                return True
        for r in list(self.queue):
            if r.rid == rid:
                self.queue.remove(r)
                if r._resume is not None:
                    # preempted-and-parked: the pool holds its snapshot
                    # on the host tier (and, enc-dec, its cross ref)
                    self.pool.drop_host(rid)
                    r._resume = None
                self._drop_request(r, st)
                return True
        if st is not None or any(r.rid == rid for r in self.finished):
            return False                # already finished / cancelled
        raise KeyError(f"request {rid}: unknown rid (never submitted)")

    def _drop_request(self, req: Request, st: StreamState | None):
        """Shared tail of both cancel paths: per-rid key caches, the
        terminal stamp, the stream transition, the metric."""
        if self.kv_layout == "paged":
            self._prompt_keys.pop(req.rid, None)
            self._frames_keys.pop(req.rid, None)
        req.done = True
        req.t_done = _now()
        self._cancelled += 1
        if st is not None:
            st.cancel()

    def _fail_streams(self, exc: BaseException):
        """Move every still-live stream to the error state (undrained
        strict run): blocked consumers raise StreamError, never hang."""
        for st in self._streams.values():
            if st.status == "live":
                st.fail(exc)

    def reset_metrics(self):
        """Zero the throughput/latency/occupancy counters (compiled steps
        and cache state are kept). Benchmarks call this between a warmup
        pass — which pays all the jit compiles — and the measured pass."""
        self.finished = []
        self._occ_sum = 0.0
        self._occ_n = 0
        self._occ_peak = 0.0
        self._tokens_out = 0
        self._steps = 0
        self._wall = 0.0
        self._launches.clear()
        self._prefill_valid = 0
        self._prefill_computed = 0
        self._slab_resets = 0
        self._spec_windows = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._preemptions = 0
        self._resumes = 0
        self._offload_bytes = 0
        self._onload_bytes = 0
        self._undrained_runs = 0
        self._cancelled = 0
        self.drained = True
        # terminal stream states go with the finished list they mirror;
        # live ones (in-flight requests) survive the reset
        self._streams = {rid: st for rid, st in self._streams.items()
                         if st.status == "live"}
        if self.kv_layout == "paged":
            st = self.pool.stats
            st.peak_pages_in_use = st.pages_in_use
            st.admission_denials = 0
            st.offload_calls = 0
            st.onload_calls = 0
            st.peak_host_pages = st.host_pages_in_use
            st.prefix_lookups = 0
            st.prefix_hits = 0
            st.prefix_evictions = 0
            st.peak_slabs_in_use = st.slabs_in_use
            st.peak_cross_in_use = st.cross_in_use
            st.cross_lookups = 0
            st.cross_hits = 0
            st.cross_evictions = 0
            self._prefix_hits = 0
            self._prefill_skipped = 0
            self._cow_copies = 0

    def metrics(self) -> dict:
        """Throughput/latency/occupancy counters for the work so far."""
        lat = [r.t_done - r.t_enqueue for r in self.finished]
        ttft = [r.t_first_token - r.t_enqueue for r in self.finished]
        wait = [r.t_admitted - r.t_enqueue for r in self.finished]
        # bytes follow the layout actually allocated: cache dtype, or the
        # codes+scale quantized layout when rt.kv_quant is set. The
        # decoder pattern holds the serving state, so byte helpers see
        # the decode-side cfg; slab/cross regions bill per sequence /
        # per distinct input rather than per token.
        per_tok = kv_bytes_per_token(self._decode_cfg, self.kv_cache_dtype,
                                     kv_scheme=self.kv_scheme)
        slab_bytes = ssm_state_bytes_per_seq(self._decode_cfg,
                                             self.kv_cache_dtype)
        cross_bytes = cross_kv_bytes_per_seq(self._decode_cfg,
                                             self.kv_cache_dtype)
        if self.kv_layout == "paged":
            st = self.pool.stats
            # a page bills what the device holds for it (lane-padded
            # scale rows included); offloaded pages keep that layout
            one_page = page_bytes(self._decode_cfg, self.page_size,
                                  self.kv_cache_dtype,
                                  kv_scheme=self.kv_scheme)
            peak_kv = st.peak_pages_in_use * one_page
            peak_state = (peak_kv
                          + st.peak_slabs_in_use * slab_bytes
                          + st.peak_cross_in_use * cross_bytes)
            paged = {"page_size": self.page_size,
                     "n_pages": self.pool.n_pages,
                     "pages_per_seq": self.pages_per_seq,
                     "peak_kv_pages": st.peak_pages_in_use,
                     "admission_denials":
                         st.admission_denials,
                     "prefill_chunk": self.prefill_chunk,
                     # state-cache regions beyond token KV: SSM slabs
                     # (one per live sequence) and cross entries (one
                     # per live distinct encoder input)
                     "n_slabs": st.n_slabs,
                     "slabs_in_use": st.slabs_in_use,
                     "peak_slabs": st.peak_slabs_in_use,
                     "slab_bytes_per_seq": int(slab_bytes),
                     # recurrent state held now, and slabs zeroed for
                     # fresh admissions
                     "slab_bytes_resident": int(st.slabs_in_use
                                                * slab_bytes),
                     "slab_resets": self._slab_resets,
                     "n_cross": st.n_cross,
                     "cross_in_use": st.cross_in_use,
                     "peak_cross": st.peak_cross_in_use,
                     "cross_bytes_per_entry": int(cross_bytes),
                     "cross_lookups": st.cross_lookups,
                     "cross_hits": st.cross_hits,
                     "cross_evictions": st.cross_evictions,
                     # continuous-batching scheduler: preempt/resume
                     # traffic and the two-tier memory picture
                     "preemptions": self._preemptions,
                     "resumes": self._resumes,
                     "offload_bytes": self._offload_bytes,
                     "onload_bytes": self._onload_bytes,
                     "host_pages": self.pool.host_pages,
                     "host_pages_in_use": st.host_pages_in_use,
                     "peak_host_pages": st.peak_host_pages,
                     "peak_host_bytes": st.peak_host_pages * one_page,
                     # prefix-cache economics (pool-side counters)
                     "prefix_cache_pages": self.pool.cache_pages,
                     "prefix_lookups": st.prefix_lookups,
                     "prefix_evictions": st.prefix_evictions,
                     "prefix_hit_rate":
                         st.prefix_hits / st.prefix_lookups
                         if st.prefix_lookups else 0.0,
                     "prefix_cache": self.prefix_cache,
                     "prefix_hits": self._prefix_hits,
                     "prefill_tokens_skipped": self._prefill_skipped,
                     "cow_copies": self._cow_copies,
                     "spec_decode": bool(self.spec_k),
                     "spec_k": self.spec_k,
                     # drafts accepted per drafted window (one window =
                     # one slot that proposed >= 1 draft this tick) /
                     # per proposed draft token — 0.0 until one ran
                     "accepted_per_step":
                         self._spec_accepted / self._spec_windows
                         if self._spec_windows else 0.0,
                     "draft_acceptance_rate":
                         self._spec_accepted / self._spec_proposed
                         if self._spec_proposed else 0.0}
            if self._has_pages:
                # per-shard budget (planner): how one model shard's slice
                # of the pool actually bills. shards=1 degenerates to the
                # global numbers.
                dcfg = self._decode_cfg
                budget = planner.plan_shard_budget(
                    dcfg.n_kv_heads, dcfg.dh, shards=self.shards,
                    page_size=self.page_size, n_pages=self.pool.n_pages,
                    n_layers=self._paged_layers(),
                    slab_bytes=int(slab_bytes),
                    act_bytes=self.kv_cache_dtype.itemsize,
                    kv_scheme=self.kv_scheme)
                paged.update(
                    kv_sharded=budget.kv_sharded,
                    kv_heads_per_shard=budget.kv_heads_per_shard,
                    pool_bytes_per_shard=budget.pool_bytes,
                    peak_kv_bytes_per_shard=int(
                        st.peak_pages_in_use * budget.page_bytes))
        else:
            # dense bills every slot its worst case up front: max_seq of
            # token KV plus the full recurrent slab and a private cross
            # block per slot, whether or not a request ever lands there
            peak_kv = self.batch_slots * self.max_seq * per_tok
            peak_state = self.batch_slots * (self.max_seq * per_tok
                                             + slab_bytes + cross_bytes)
            paged = {}
        return {
            "kv_layout": self.kv_layout,
            "scheduler": self.scheduler,
            "shards": self.shards,
            "undrained_runs": self._undrained_runs,
            "drained": self.drained,
            "kv_scheme": self.kv_scheme or "none",
            # what the cache arrays actually hold: the quantized layout
            # ignores kv_cache_dtype (codes are uint8, scales f32)
            "kv_cache_dtype": ("uint8+f32scale" if self.kv_scheme
                               else self.kv_cache_dtype.name),
            "requests_finished": len(self.finished),
            "requests_cancelled": self._cancelled,
            "tokens_generated": self._tokens_out,
            "engine_steps": self._steps,
            "model_calls": sum(self._launches.values()),
            # launches by program: prefill, decode, dense_prefill,
            # dense_decode, encode (enc-dec cross entries)
            "launches": dict(self._launches),
            # prompt tokens over rows computed by the prefill launches:
            # the share not spent on padding and idle slots
            "prefill_row_util": self._prefill_valid / self._prefill_computed
            if self._prefill_computed else 0.0,
            "wall_s": self._wall,
            "tokens_per_s": self._tokens_out / self._wall
            if self._wall else 0.0,
            "ttft_p50_ms": 1e3 * float(np.median(ttft)) if ttft else 0.0,
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95))
            if ttft else 0.0,
            "latency_p50_ms": 1e3 * float(np.median(lat)) if lat else 0.0,
            "latency_p95_ms": 1e3 * float(np.percentile(lat, 95))
            if lat else 0.0,
            # submission -> first placed in a slot
            "queue_wait_p50_ms": 1e3 * float(np.median(wait))
            if wait else 0.0,
            "queue_wait_p95_ms": 1e3 * float(np.percentile(wait, 95))
            if wait else 0.0,
            "occupancy_mean": self._occ_sum / self._occ_n
            if self._occ_n else 0.0,
            "occupancy_peak": self._occ_peak,
            "peak_kv_bytes": int(peak_kv),
            # the unified bill: token KV + SSM slabs + cross entries —
            # comparable across layouts and architectures
            "peak_state_bytes": int(peak_state),
            **paged,
        }

    # -- paged internals -----------------------------------------------------

    def _match_prefix(self, req: Request):
        """-> (shared full pages, COW source page or None, matched tokens).

        The pool's index matches page-aligned full pages of the prompt.
        When the *whole* prompt is covered (page-aligned identical
        prompt), the last matched page cannot simply be mapped: the final
        prompt token must be re-run to produce first-token logits, and
        its KV write would land in the shared page. That page becomes a
        copy-on-write source instead — admission copies it into a private
        fresh page and prefill resumes at the last token. Matched tokens
        are therefore always < len(prompt), so every admitted request
        flows through the normal prefill-completion path."""
        keys = self._prompt_keys.get(req.rid)
        if keys is None:
            keys = self._prompt_keys[req.rid] = \
                self.pool.prompt_keys(req.prompt)
        cand = self.pool.match_prefix(req.prompt, keys=keys)
        if not cand:
            return [], None, 0
        matched = len(cand) * self.page_size
        if matched < len(req.prompt):
            return cand, None, matched
        return cand[:-1], cand[-1], len(req.prompt) - 1

    def _admit_paged(self):
        """Admission is page-budget-based either way: a request enters a
        slot only when the pool covers its worst-case token footprint
        (prompt + max_new, capped at max_seq — reserved up front so
        decode can never OOM mid-sequence) minus any shared-prefix pages
        the prefix cache maps in place of fresh ones
        (``planner.plan_seq_pages``). The *policy* differs:

        * ``fifo`` — the original synchronous baseline: strict submit
          order, a blocked head blocks the queue (no starvation of long
          prompts by short ones, no preemption).
        * ``cb`` — continuous batching: candidates are tried in
          (priority desc, submit order) order, the first that fits is
          admitted (skip-ahead keeps slots busy), and when nothing fits
          the top candidate may preempt strictly-lower-priority
          residents — their written KV pages offload to the host tier
          and they resume later from the exact write cursor.
        """
        if self.scheduler == "cb":
            self._admit_cb()
        else:
            self._admit_fifo()

    def _admit_fifo(self):
        for slot in range(self.batch_slots):
            if not self.queue:
                return
            if self.slot_req[slot] is not None:
                continue
            if not self._try_admit(self.queue[0], slot):
                return                      # wait for a release

    def _admit_cb(self):
        while self.queue:
            free = [s for s in range(self.batch_slots)
                    if self.slot_req[s] is None]
            order = sorted(self.queue,
                           key=lambda r: (-r.priority, r._seq))
            admitted = False
            if free:
                for req in order:
                    if self._try_admit(req, free[0]):
                        admitted = True
                        break
            if admitted:
                continue
            # nobody fits as-is: preempt on behalf of the top candidate
            # only (preempting for a skip-ahead candidate could evict
            # work the top one is about to need), then admit it straight
            # away — every loop iteration either admits or returns, so
            # admission can never spin on a preemption that didn't pay
            if not self._preempt_for(order[0]):
                return
            slot = next(s for s in range(self.batch_slots)
                        if self.slot_req[s] is None)
            if not self._try_admit(order[0], slot):
                return

    def _try_admit(self, req: Request, slot: int) -> bool:
        """Try to place ``req`` into the free ``slot``: fresh admission
        (prefix-cache matching included) or resume-from-offload when the
        request carries preemption state. Pops it from the queue and
        returns True on success; False leaves every piece of state — the
        queue, the pool, the slot — untouched."""
        if req._resume is not None:
            return self._try_resume(req, slot)
        shared, cow_src, matched = ([], None, 0)
        if self.prefix_cache:
            shared, cow_src, matched = self._match_prefix(req)
        kv_tokens = (self._worst_case_tokens(req)
                     if self._has_pages else 0)
        pages = self.pool.allocate(req.rid, kv_tokens,
                                   shared_prefix=shared,
                                   need_slab=self._has_slab,
                                   cross_key=self._frames_key(req))
        if pages is None:                    # NOT truthiness: a pageless
            return False                     # success returns []
        if cow_src is not None:
            # private copy of the partially-reused last page; the
            # re-run final token overwrites its own (identical) KV
            self.caches = self._copy_page(
                self.caches, jnp.int32(cow_src),
                jnp.int32(pages[len(shared)]))
            self._cow_copies += 1
        if matched:
            self._prefix_hits += 1
            self._prefill_skipped += matched
        if self._has_slab:
            # a fresh sequence starts from zero recurrent state; the
            # slab index is recycled, so the zero is explicit
            self.caches = self._reset_slabs(
                self.caches, jnp.int32(self.pool.seq_slab(req.rid)))
            self._slab_resets += 1
        if self._has_cross and self.pool.consume_cross_fresh(req.rid):
            # cross-region miss: run the encoder + per-slot K/V
            # projection once and fill the claimed entry. A hit (same
            # frames as a live or cached entry) skips this entirely —
            # the whole encoder pass is reused.
            entries = self._encode_cross(
                self.params,
                jnp.asarray(req.frames, jnp.float32)[None],
                self.cfg, self.rt)
            self._launches["encode"] += 1
            self.caches = self._fill_cross(
                self.caches, jnp.int32(self.pool.seq_cross(req.rid)),
                entries)
        self.queue.remove(req)
        req.t_admitted = _now()
        self.slot_req[slot] = req
        self.slot_pos[slot] = matched
        self._fed[slot] = matched
        self._set_block_row(slot, req.rid)
        self._sync_state_idx(slot, req.rid)
        if self.spec_k:
            # the drafter indexes the FULL prompt (matched prefix
            # included) — sharing changes where KV bytes live, not
            # what n-grams the sequence's history contains
            self.drafter.start(req.rid, req.prompt)
        return True

    # -- preemption + resume (cb scheduler; docs/SERVING.md lifecycle) -------

    def _snapshot_pages(self, pages: tuple[int, ...]):
        """Gather the written pages' bytes to host (the offload payload).
        Indices pad to a power of two by repeating the last page so
        O(log) compiles cover every preemption; the duplicates are
        sliced off after the device_get."""
        n = len(pages)
        n_pad = _pad_pow2(n, self.pages_per_seq)
        idx = np.full(n_pad, pages[-1], np.int32)
        idx[:n] = pages
        snap = jax.device_get(self._gather_pages(self.caches,
                                                 jnp.asarray(idx)))
        return jax.tree_util.tree_map(lambda leaf: leaf[:, :n], snap)

    def _restore_pages(self, pages: list[int], payload):
        """Scatter an offload payload into freshly allocated pages (the
        first ``n`` of the new reservation, in logical order). Padding
        duplicates the last (index, payload row) pair, so duplicate
        scatter writes carry identical bytes — deterministic."""
        n = jax.tree_util.tree_leaves(payload)[0].shape[1]
        n_pad = _pad_pow2(n, self.pages_per_seq)
        idx = np.full(n_pad, pages[n - 1], np.int32)
        idx[:n] = pages[:n]
        if n_pad > n:
            payload = jax.tree_util.tree_map(
                lambda leaf: np.concatenate(
                    [leaf, np.repeat(leaf[:, -1:], n_pad - n, axis=1)],
                    axis=1),
                payload)
        self.caches = self._scatter_pages(self.caches, jnp.asarray(idx),
                                          payload)

    def _preempt_slot(self, slot: int) -> bool:
        """Evict the resident request: snapshot the pages covering its
        write cursor, park them (and the bytes) on the pool's host tier,
        release its device pages ref-aware, and requeue it carrying
        resume state. Returns False (state untouched) when the host tier
        cannot take the pages. Everything past the write cursor —
        unwritten reservation, rejected speculative tails — is garbage
        that was never attended, so it is deliberately not snapshotted."""
        req = self.slot_req[slot]
        n_written = int(self.slot_pos[slot])
        fed = int(self._fed[slot])
        if self._has_pages:
            _, n_keep = planner.plan_resume_pages(
                n_written, self._worst_case_tokens(req), self.page_size)
        else:
            n_keep = 0
        page_payload = (
            self._snapshot_pages(self.pool.seq_pages(req.rid)[:n_keep])
            if n_keep else None)
        slab_payload = None
        if self._has_slab:
            # the slab is the sequence's entire recurrent state — O(1)
            # in written tokens, always snapshotted whole
            slab_payload = jax.device_get(self._gather_slabs(
                self.caches, jnp.int32(self.pool.seq_slab(req.rid))))
        payload = ((page_payload, slab_payload)
                   if page_payload is not None or slab_payload is not None
                   else None)
        if self.pool.offload(req.rid, n_keep, payload) is None:
            return False                    # host tier full
        for part in (page_payload, slab_payload):
            if part is not None:
                self._offload_bytes += sum(
                    leaf.nbytes
                    for leaf in jax.tree_util.tree_leaves(part))
        req._resume = (n_written, fed)
        req.preemptions += 1
        self._preemptions += 1
        self.slot_req[slot] = None
        self.block_tables[slot] = 0
        self.slot_pos[slot] = 0
        self._fed[slot] = -1
        self._state_idx[slot] = (self._n_slabs, self._n_cross)
        if self.spec_k:
            # the n-gram index rebuilds deterministically from
            # prompt + output at resume — nothing to keep
            self.drafter.drop(req.rid)
        self.queue.append(req)
        return True

    def _try_resume(self, req: Request, slot: int) -> bool:
        """Bring a preempted request back: fresh worst-case reservation
        (no prefix sharing — the restored bytes are private), scatter the
        host snapshot into the new pages, and re-enter the tick loop at
        the exact (write cursor, prefill progress) it was evicted at."""
        n_written, fed = req._resume
        kv_tokens = (self._worst_case_tokens(req)
                     if self._has_pages else 0)
        res = self.pool.onload(req.rid, kv_tokens)
        if res is None:
            return False                    # device capacity still short
        pages, payload = res
        page_payload, slab_payload = (payload if payload is not None
                                      else (None, None))
        if page_payload is not None:
            self._restore_pages(pages, page_payload)
            self._onload_bytes += sum(
                leaf.nbytes
                for leaf in jax.tree_util.tree_leaves(page_payload))
        if slab_payload is not None:
            # the reacquired slab index may differ from the one held at
            # offload — scatter wherever the pool now points
            self.caches = self._scatter_slabs(
                self.caches, jnp.int32(self.pool.seq_slab(req.rid)),
                slab_payload)
            self._onload_bytes += sum(
                leaf.nbytes
                for leaf in jax.tree_util.tree_leaves(slab_payload))
        self.queue.remove(req)
        req._resume = None
        self._resumes += 1
        self.slot_req[slot] = req
        self.slot_pos[slot] = n_written
        self._fed[slot] = fed
        self._set_block_row(slot, req.rid)
        self._sync_state_idx(slot, req.rid)
        if self.spec_k:
            # deterministic rebuild: the incremental index over
            # prompt + emitted output is a pure function of both
            self.drafter.start(req.rid, req.prompt)
            for tok in req.output:
                self.drafter.extend(req.rid, int(tok))
        return True

    def _preempt_for(self, cand: Request) -> bool:
        """Preempt strictly-lower-priority residents until ``cand`` has a
        slot and enough free pages, lowest priority first (youngest
        breaking ties — they lose the least progress). Prechecked against
        both tiers before any eviction: the chosen victims' releasable
        pages (shared pages with other owners free nothing) must cover
        the candidate's worst-case need, and the host tier must have room
        for every victim's written pages — a half-done preemption wave
        would evict work without admitting anyone. Equal priorities never
        preempt: that is what keeps cb admission FIFO-compatible (and
        livelock-free — the highest-priority resident always runs)."""
        need = (planner.plan_seq_pages(self._worst_case_tokens(cand),
                                       self.page_size)
                if self._has_pages else 0)
        victims = sorted(
            (s for s, r in enumerate(self.slot_req)
             if r is not None and r.priority < cand.priority),
            key=lambda s: (self.slot_req[s].priority,
                           -self.slot_req[s]._seq))
        free_slot = any(r is None for r in self.slot_req)
        gain = self.pool.free_pages()
        host_extra = 0
        chosen: list[int] = []
        for s in victims:
            if gain >= need and (free_slot or chosen):
                break
            if self._has_pages:
                _, n_keep = planner.plan_resume_pages(
                    int(self.slot_pos[s]),
                    self._worst_case_tokens(self.slot_req[s]),
                    self.page_size)
            else:
                n_keep = 0
            if (self.pool.host_pages is not None
                    and self.pool.stats.host_pages_in_use + host_extra
                    + n_keep > self.pool.host_pages):
                continue                    # host tier can't take this one
            chosen.append(s)
            gain += self.pool.releasable_pages(self.slot_req[s].rid)
            host_extra += n_keep
        if gain < need or not (free_slot or chosen):
            return False
        preempted = False
        for s in chosen:
            preempted |= self._preempt_slot(s)
        return preempted

    def preempt(self, rid: int):
        """Force-preempt a resident request (fault injection / tests —
        the cb scheduler calls ``_preempt_for`` itself). Raises KeyError
        when ``rid`` is not resident, RuntimeError when the host tier
        cannot take its pages."""
        if self.kv_layout != "paged":
            raise ValueError("preempt() needs kv_layout='paged'")
        for slot, r in enumerate(self.slot_req):
            if r is not None and r.rid == rid:
                if not self._preempt_slot(slot):
                    raise RuntimeError(
                        f"request {rid}: host tier full "
                        f"({self.pool.stats.host_pages_in_use}/"
                        f"{self.pool.host_pages} pages)")
                return
        raise KeyError(f"request {rid} is not resident in any slot")

    def _launch(self, kind: str, program: str, call, *, rows: int,
                tokens: int, **args) -> np.ndarray:
        """Run one model program and bring its logits to the host.
        ``call()`` packs and uploads the inputs, calls the jitted step and
        returns its logits; ``kind`` ("prefill" or "decode") names the
        spans, ``program`` the launch counter. ``rows``: rows the program
        computes; ``tokens``: the valid ones among them."""
        with span(f"serve.{kind}.launch", rows=rows, tokens=tokens, **args):
            logits = call()
        self._launches[program] += 1
        if kind == "prefill":
            self._prefill_valid += tokens
            self._prefill_computed += rows
        with span(f"serve.{kind}.wait", bytes=logits.nbytes):
            return np.asarray(logits)

    def _paged_launch(self, kind: str, step, width: int,
                      feed: dict) -> np.ndarray:
        """One paged step (the ``kind`` program: prefill or decode) over a
        ``(batch_slots, width)`` token block. ``feed``: slot -> (tokens
        already in its pages, its next tokens); the other rows ride along
        masked (n_valid = 0)."""
        def call():
            tokens = np.zeros((self.batch_slots, width), np.int32)
            ctx = np.zeros(self.batch_slots, np.int32)
            n_valid = np.zeros(self.batch_slots, np.int32)
            for i, (pos, toks) in feed.items():
                tokens[i, :len(toks)] = toks
                ctx[i] = pos
                n_valid[i] = len(toks)
            logits, self.caches = step(
                self.params, jnp.asarray(tokens), jnp.asarray(ctx),
                jnp.asarray(self.block_tables), jnp.asarray(n_valid),
                jnp.asarray(self._state_idx), self.caches, self.cfg,
                self.rt)
            return logits
        args = {"c_pad": width} if kind == "prefill" else {}
        return self._launch(kind, kind, call,
                            rows=self.batch_slots * width,
                            tokens=sum(len(t) for _, t in feed.values()),
                            **args)

    def _prefill_tick(self):
        """Advance every prefilling slot by one prompt chunk in a single
        batched call (per-row ctx_len/n_valid make ragged rows legal —
        same mechanism the decode step uses; non-prefilling rows ride
        along masked with n_valid=0). Interleaved with the batch decode
        step so running sequences keep producing tokens."""
        rows = [i for i in range(self.batch_slots)
                if self.slot_req[i] is not None and self._fed[i] >= 0]
        if not rows:
            return
        with span("serve.prefill"):
            feed = {}
            for i in rows:
                fed = int(self._fed[i])
                feed[i] = (fed, self.slot_req[i].prompt[
                    fed:fed + self.prefill_chunk])
            c_pad = _pad_pow2(max(len(t) for _, t in feed.values()),
                              self.prefill_chunk)
            logits = self._paged_launch("prefill", self._paged_step, c_pad,
                                        feed)
            with span("serve.prefill.emit"):
                for i in rows:
                    self._after_chunk(i, len(feed[i][1]), logits[i])

    def _after_chunk(self, slot: int, n: int, logits: np.ndarray):
        """Bookkeeping after ``n`` more prompt tokens of ``slot`` went
        through the model; the last chunk picks the first token."""
        req = self.slot_req[slot]
        self._fed[slot] += n
        self.slot_pos[slot] = self._fed[slot]
        if self.prefix_cache:
            # index every prompt page this chunk completed — full prompt
            # pages are immutable from here on, so queued requests with
            # the same prefix can start sharing them on the very next
            # admission tick (before _maybe_finish: a released page stays
            # indexed and revivable)
            self.pool.register_prefix(
                req.rid, req.prompt, int(self._fed[slot]),
                keys=self._prompt_keys.get(req.rid))
        if self._fed[slot] == len(req.prompt):
            self._fed[slot] = -1            # -> decoding
            first = self._pick_token(logits, req)
            req.output.append(int(first))
            self._tokens_out += 1
            if self.spec_k:
                self.drafter.extend(req.rid, int(first))
            req.t_first_token = _now()
            self._maybe_finish(slot)        # max_new_tokens == 1

    def _decode_step_paged(self):
        """One decode tick for every decoding slot, in one launch of the
        decode program (``lm_paged_fused_step``) over a fixed window W per
        row: the pending token plus this slot's drafts (``n_valid``
        carries the ragged part). W = spec_k + 1, or 1 without
        speculation, so drafted and undrafted ticks share one compile;
        every layer's attention is one ``paged_decode_ragged`` launch.

        Each row keeps its longest accepted prefix plus a bonus or
        correction token (``_accept_tokens``; with no drafts that IS the
        plain greedy/sampled pick, same key chain), so greedy outputs
        are identical with speculation on or off — regression-tested.
        Rollback is cursor arithmetic: ``slot_pos`` advances only past
        accepted tokens; a rejected tail's page slots are overwritten by
        the next window at those positions and never attended
        (``attend_len`` masks them)."""
        active = [i for i, r in enumerate(self.slot_req)
                  if r is not None and self._fed[i] < 0]
        if not active:
            return
        with span("serve.decode"):
            drafts = {i: self._propose(i) for i in active}
            w = (self.spec_k + 1) if self.spec_k else 1
            feed = {i: (int(self.slot_pos[i]),
                        [self.slot_req[i].output[-1]] + drafts[i])
                    for i in active}
            logits = self._paged_launch("decode", self._fused_step, w,
                                        feed)
            with span("serve.decode.emit"):
                for i in active:
                    self._emit_window(i, drafts[i], logits[i])

    def _emit_window(self, slot: int, drafts: list[int],
                     logits: np.ndarray):
        """Emit what one verified window (``logits``: (W, V)) accepts
        and move the slot's write cursor past it."""
        req = self.slot_req[slot]
        emitted = self._accept_tokens(req, drafts, logits)
        accepted = len(emitted) - 1                  # drafts kept
        for tok in emitted:
            req.output.append(int(tok))
            if self.spec_k:
                # keep the n-gram history == prompt + output even on
                # undrafted ticks, or later proposals would continue
                # from a stale tail
                self.drafter.extend(req.rid, int(tok))
        self._tokens_out += len(emitted)
        if drafts:
            self._spec_windows += 1
        self._spec_proposed += len(drafts)
        self._spec_accepted += accepted
        # KV rollback: pending token + accepted drafts stay; the write
        # cursor retreats past any rejected tail
        self.slot_pos[slot] += 1 + accepted
        self._maybe_finish(slot)

    # -- speculative decoding (serving/spec.py has the drafter) --------------

    def _propose(self, slot: int) -> list[int]:
        """The slot's draft tokens this tick (none without speculation or
        without room)."""
        if not self.spec_k:
            return []
        req = self.slot_req[slot]
        room = self._draft_room(req, int(self.slot_pos[slot]))
        return (self.drafter.propose(req.rid, min(self.spec_k, room))
                if room > 0 else [])

    def _draft_room(self, req: Request, pos: int) -> int:
        """Max draft tokens this window may carry. Two caps: the window
        emits up to d+1 tokens (never past max_new_tokens) and writes
        positions pos..pos+d (never past the positions a non-speculative
        decode could reach, so the worst-case page reservation still
        covers every write)."""
        return min(req.max_new_tokens - len(req.output),
                   self.max_seq - 1 - pos) - 1

    def _accept_tokens(self, req: Request, drafts: list[int],
                       logits: np.ndarray) -> list[int]:
        """Tokens to emit for one verified window. ``logits``: (W, V),
        position j scored after window token j. Greedy: longest prefix of
        drafts matching argmax, then the correction (first mismatch) or
        bonus (all matched) token — by construction exactly the sequence
        non-speculative greedy decode would emit. Temperature: per-draft
        rejection sampling against the target distribution; the drafter
        is deterministic (a point mass), so acceptance of draft t is a
        Bernoulli(p[t]) draw and a rejection resamples from the residual
        p with t removed — the emitted token is still distributed per
        the target model. All draws come from the request's own key
        chain."""
        if req.temperature <= 0:
            out = []
            for j, t in enumerate(drafts):
                top = int(np.argmax(logits[j]))
                if top != t:
                    return out + [top]               # correction
                out.append(t)
            out.append(int(np.argmax(logits[len(drafts)])))  # bonus
            return out
        out = []
        for j, t in enumerate(drafts):
            p = _softmax_np(logits[j], req.temperature)
            req.key, sub = jax.random.split(req.key)
            if float(jax.random.uniform(sub)) < p[t]:
                out.append(t)
                continue
            # rejected: resample from the residual (p minus the point
            # mass at t, renormalized)
            res = p.copy()
            res[t] = 0.0
            z = res.sum()
            req.key, sub = jax.random.split(req.key)
            if z <= 0.0:                             # p was ~all at t
                return out + [int(np.argmax(logits[j]))]
            return out + [int(jax.random.choice(sub, res.shape[0],
                                                p=jnp.asarray(res / z)))]
        out.append(self._pick_token(logits[len(drafts)], req))  # bonus
        return out

    def _maybe_finish(self, slot: int):
        req = self.slot_req[slot]
        if (len(req.output) >= req.max_new_tokens
                or self.slot_pos[slot] >= self.max_seq - 1):
            self._finish(slot)

    def _finish(self, slot: int):
        req = self.slot_req[slot]
        req.done = True
        req.t_done = _now()
        self.finished.append(req)
        st = self._streams.get(req.rid)
        if st is not None:
            st.finish()
        self.slot_req[slot] = None
        if self.kv_layout == "paged":
            # release recycles zero-ref pages, returns the slab to the
            # free list and drops the cross reference (a zero-ref cross
            # entry stays indexed — cached-free, revivable by key)
            self.pool.release(req.rid)
            self.block_tables[slot] = 0
            self._fed[slot] = -1
            self._state_idx[slot] = (self._n_slabs, self._n_cross)
            self._prompt_keys.pop(req.rid, None)
            self._frames_keys.pop(req.rid, None)
            if self.spec_k:
                self.drafter.drop(req.rid)

    # -- dense internals -----------------------------------------------------

    def _admit_dense(self):
        for slot in range(self.batch_slots):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue.pop(0)
                req.t_admitted = _now()
                self.slot_req[slot] = req
                with span("serve.prefill"):
                    n = len(req.prompt)
                    logits = self._launch(
                        "prefill", "dense_prefill",
                        lambda: self._prefill_slot(slot, req),
                        rows=n, tokens=n, c_pad=n)
                    with span("serve.prefill.emit"):
                        self.slot_pos[slot] = n
                        first = self._pick_token(logits[0], req)
                        req.output.append(int(first))
                        self._tokens_out += 1
                        req.t_first_token = _now()
                        self._maybe_finish(slot)    # max_new_tokens == 1

    def _prefill_slot(self, slot: int, req: Request):
        """Prefill ``slot`` with the request's prompt: run it through a
        single-row batch, splice the row's caches into the engine batch
        at ``slot``, return the last position's logits."""
        tok = jnp.asarray(req.prompt, jnp.int32)[None, :]
        if self.cfg.enc_dec:
            row_caches = encdec_mod.encdec_init_caches(
                self.cfg, 1, self.max_seq, dtype=self.kv_cache_dtype,
                kv_quant=self.rt.kv_quant)
            frames = jnp.asarray(req.frames, jnp.float32)[None]
            logits, row_caches = self._prefill_one(
                self.params, frames, tok, row_caches, self.cfg, self.rt)
        else:
            row_caches = lm_mod.init_caches(
                self.cfg, 1, self.max_seq, dtype=self.kv_cache_dtype,
                kv_quant=self.rt.kv_quant)
            logits, row_caches = self._prefill_one(
                self.params, tok, row_caches, self.cfg, self.rt)
        self.caches = _splice_caches(self.caches, row_caches, slot)
        return logits

    def _decode_step_dense(self):
        active = [i for i, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return
        with span("serve.decode"):
            def call():
                tokens = np.zeros(self.batch_slots, np.int32)
                for i in active:
                    tokens[i] = self.slot_req[i].output[-1]
                # continuous batching: each slot decodes at its own
                # position
                logits, self.caches = self._decode(
                    self.params, jnp.asarray(tokens),
                    jnp.asarray(self.slot_pos, jnp.int32), self.caches,
                    self.cfg, self.rt)
                return logits
            logits = self._launch("decode", "dense_decode", call,
                                  rows=self.batch_slots,
                                  tokens=len(active))
            with span("serve.decode.emit"):
                for i in active:
                    req = self.slot_req[i]
                    tok = self._pick_token(logits[i], req)
                    req.output.append(int(tok))
                    self._tokens_out += 1
                    self.slot_pos[i] += 1
                    self._maybe_finish(i)

    # -- shared --------------------------------------------------------------

    def _pick_token(self, row: np.ndarray, req: Request) -> int:
        if req.temperature <= 0:
            return int(np.argmax(row))
        # per-request chain: the draw sequence is a function of this
        # request alone, so sampled outputs are invariant to submit
        # order, slot assignment and batch-mates (regression-tested)
        req.key, sub = jax.random.split(req.key)
        return int(jax.random.categorical(sub, jnp.asarray(row)
                                          / req.temperature))


def _softmax_np(row: np.ndarray, temperature: float) -> np.ndarray:
    """Stable softmax over a logits row (f64 — host-side acceptance
    probabilities for speculative rejection sampling)."""
    x = np.asarray(row, np.float64) / temperature
    x = x - x.max()
    p = np.exp(x)
    return p / p.sum()


def _splice_caches(batch_caches, row_caches, slot: int):
    """Insert a prefilled single-row cache at batch index ``slot``. Cache
    leaves have layout (P, B, ...)."""
    def splice(bc, rc):
        return bc.at[:, slot:slot + 1].set(rc)
    return jax.tree_util.tree_map(splice, batch_caches, row_caches)
