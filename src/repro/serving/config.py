"""One frozen config object for every serving knob.

``ServeConfig`` holds every ``ServeEngine`` knob in a single frozen
dataclass; ``ServeConfig.resolve(cfg)`` is the ONLY place env fallbacks
are read and cross-knob validation runs, and it returns a fully-resolved
copy (every ``None``/"auto" replaced by the concrete value the engine
will use). The engine, the replica router, benches and the CLI all
construct from the same resolved object, so a knob combination is legal
or illegal in exactly one place.

Resolution contract:

* ``None`` means "read the env default, else the built-in default".
* An env-enabled feature **degrades silently** where the architecture or
  layout can't support it (e.g. ``REPRO_PREFIX_CACHE=1`` on a dense
  engine); an **explicit** ``True``/value there is a caller error with
  the failing predicate(s) enumerated.
* ``resolve()`` is idempotent: resolving a resolved config returns it
  unchanged, so plumbing can resolve defensively.
* ``fused_decode`` is no choice: there is one decode path (the ragged
  decode kernel), ``None`` and ``True`` both resolve to it and ``False``
  raises.

Env knobs owned here: ``REPRO_PREFIX_CACHE``, ``REPRO_SPEC_K``,
``REPRO_SCHEDULER``, ``REPRO_HOST_PAGES``, ``REPRO_PREFIX_CACHE_PAGES``,
``REPRO_PREFILL_CHUNK``, ``REPRO_SHARDS``, ``REPRO_REPLICAS``.
(``REPRO_PAGE_SIZE`` stays with the planner: it pins the *planned* page
size for every consumer of ``plan_kv_pages``, not just the engine.)

Sharding knobs (docs/SERVING.md "Sharded serving"):

* ``shards`` — tensor-parallel width: the engine builds a
  ``(data=1, model=shards)`` mesh, places params by ``ShardingPolicy``
  and head-shards the paged KV/state pools over the model axis.
* ``replicas`` — data-parallel width: a ``ReplicaRouter`` knob (the
  engine itself always runs one replica); each replica gets its own
  engine, device slice and per-replica page budget.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Optional

import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.serving.spec import DEFAULT_SPEC_K

__all__ = ["ServeConfig", "DEFAULT_PREFILL_CHUNK"]

#: chunk length for chunked prefill when the caller doesn't pass one;
#: REPRO_PREFILL_CHUNK=N overrides. Ragged final chunks are padded up to
#: the next power of two so the engine compiles O(log chunk) variants,
#: not one per prompt length.
DEFAULT_PREFILL_CHUNK = 32

def _decode_pattern_cfg(cfg: ArchConfig) -> ArchConfig:
    """The config whose layer pattern holds serving state (the DECODER
    for enc-dec models)."""
    if cfg.enc_dec:
        from repro.models import encdec as encdec_mod
        return encdec_mod.dec_cfg(cfg)
    return cfg


def _slab_mixers(dcfg: ArchConfig) -> list[str]:
    """The recurrent mixer kinds present in the decode pattern."""
    return sorted({s.split("+")[0] for s in dcfg.pattern}
                  & {"mamba", "mlstm", "slstm"})


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Every ServeEngine knob, in one frozen object. Construct with
    whatever subset you care about and let ``resolve()`` fill the rest::

        eng = ServeEngine(params, cfg, ServeConfig(batch_slots=8,
                                                   kv_layout="paged"))
    """
    batch_slots: int = 4
    max_seq: int = 256
    quantize: Optional[str] = "sp2_4"
    seed: int = 0
    kv_layout: str = "auto"
    page_size: Optional[int] = None
    pool_pages: Optional[int] = None
    prefill_chunk: Optional[int] = None
    kv_cache_dtype: Any = "float32"
    prefix_cache: Optional[bool] = None
    spec_decode: Optional[bool] = None
    spec_k: Optional[int] = None
    #: no choice left: a paged engine decodes through the ragged decode
    #: kernel, and False is an error. Kept only while the benchmark's
    #: harness passes fused_decode=True; it goes when that stops.
    fused_decode: Optional[bool] = None
    scheduler: Optional[str] = None
    host_pages: Optional[int] = None
    prefix_cache_pages: Optional[int] = None
    #: tensor-parallel width (model-axis mesh size). None = REPRO_SHARDS
    #: env, default 1 (single device).
    shards: Optional[int] = None
    #: data-parallel replica count — consumed by ReplicaRouter, rejected
    #: by a bare ServeEngine. None = REPRO_REPLICAS env, default 1.
    replicas: Optional[int] = None
    #: set by resolve(); resolved configs pass through resolve() unchanged
    resolved: bool = False

    def replace(self, **kw) -> "ServeConfig":
        """Keyword field replacement. Any change invalidates resolution —
        the copy must be resolved again."""
        kw.setdefault("resolved", False)
        return dataclasses.replace(self, **kw)

    # -- resolution ----------------------------------------------------------

    def resolve(self, cfg: ArchConfig) -> "ServeConfig":
        """Return a fully-resolved copy for ``cfg``: env fallbacks read,
        "auto" layouts picked, cross-knob gates checked. Idempotent."""
        if self.resolved:
            return self
        dcfg = _decode_pattern_cfg(cfg)
        has_slab = bool(_slab_mixers(dcfg))
        has_cross = bool(cfg.enc_dec)

        if self.batch_slots < 1:
            raise ValueError(
                f"batch_slots must be >= 1, got {self.batch_slots}")
        if self.max_seq < 1:
            raise ValueError(f"max_seq must be >= 1, got {self.max_seq}")

        kv_layout = self.kv_layout
        if kv_layout == "auto":
            # every supported pattern serves paged now (SSM, hybrid,
            # enc-dec, M-RoPE included); dense remains as the
            # differential-test baseline
            kv_layout = "paged"
        if kv_layout not in ("paged", "dense"):
            raise ValueError(
                f"kv_layout must be 'paged', 'dense' or 'auto', "
                f"got {kv_layout!r}")

        # shared-prefix KV page reuse (paged, token-KV-only patterns).
        # None = read the env default; an env-enabled cache degrades
        # silently where unsupported, an explicit True there is a caller
        # error with the actual failing predicate(s) enumerated.
        explicit_prefix = self.prefix_cache is not None
        prefix_cache = self.prefix_cache
        if prefix_cache is None:
            prefix_cache = os.environ.get(
                "REPRO_PREFIX_CACHE", "").lower() in ("1", "true")
        prefix_gaps = []
        if kv_layout != "paged":
            prefix_gaps.append("kv_layout='dense' — per-slot rows, "
                               "nothing to share")
        if has_slab:
            prefix_gaps.append(
                f"recurrent mixer(s) {_slab_mixers(dcfg)} in "
                f"pattern={dcfg.pattern} — slab state is "
                "per-sequence, not per-page")
        if has_cross:
            prefix_gaps.append(
                "enc_dec=True — decoder KV depends on the encoder "
                "output, so prompt pages are not shareable by token "
                "content (the cross region already shares the encoder "
                "pass by frames)")
        if prefix_cache and prefix_gaps:
            if explicit_prefix:
                raise ValueError(
                    "prefix_cache=True is unsupported here: "
                    + "; ".join(prefix_gaps))
            prefix_cache = False

        # speculative decoding (paged only — the verify window rides the
        # paged decode program). None = read the env default (REPRO_SPEC_K=N
        # enables with window N); passing spec_k alone also enables —
        # a window size IS the intent, silently ignoring it would let a
        # caller benchmark speculation that never ran. Mirroring
        # prefix_cache, an env-enabled default degrades silently for a
        # dense engine; an explicit spec_decode=True (or spec_k=) there
        # is a caller error.
        env_k = int(os.environ.get("REPRO_SPEC_K", "0") or 0)
        raw_k = self.spec_k
        if raw_k == 0 and self.spec_decode is False:
            # the (spec_decode=False, spec_k=0) pair is a resolved "off"
            # config that was replace()d and is being re-resolved; any
            # other explicit zero window stays a caller error below
            raw_k = None
        if raw_k is not None and raw_k < 1:
            raise ValueError(f"spec_k must be >= 1, got {raw_k}")
        if self.spec_decode is False and raw_k is not None:
            raise ValueError(
                f"spec_k={raw_k} with spec_decode=False — drop one")
        explicit_spec = (self.spec_decode is not None
                         or raw_k is not None)
        spec_decode = self.spec_decode
        if spec_decode is None:
            spec_decode = env_k > 0 or raw_k is not None
        spec_gaps = []
        if kv_layout != "paged":
            spec_gaps.append("kv_layout='dense' — the paged decode "
                             "program scores the draft window")
        if has_slab:
            spec_gaps.append(
                f"recurrent mixer(s) {_slab_mixers(dcfg)} in "
                f"pattern={dcfg.pattern} — slab updates are "
                "destructive, a rejected draft tail cannot roll back")
        if spec_decode and spec_gaps:
            if explicit_spec:
                raise ValueError("spec_decode is unsupported here: "
                                 + "; ".join(spec_gaps))
            spec_decode = False
        if spec_decode:
            spec_k = (raw_k if raw_k is not None
                      else (env_k or DEFAULT_SPEC_K))
            if spec_k < 1:
                raise ValueError(
                    f"spec_k must be >= 1, got {spec_k} "
                    "(check REPRO_SPEC_K)")
        else:
            spec_k = 0

        if self.fused_decode is False:
            raise ValueError(
                "fused_decode=False: the per-call decode path was removed "
                "— the decode tick always runs the ragged decode kernel")

        # scheduler: "cb" (continuous batching — priority admission with
        # preemption + KV offload, the paged default) or "fifo" (the
        # synchronous head-blocks-queue baseline). REPRO_SCHEDULER
        # overrides the default; an env-selected "cb" degrades silently
        # to fifo for a dense engine while an explicit one there is a
        # caller error (preemption snapshots live in the page pool — the
        # dense layout has nothing to offload).
        explicit_sched = self.scheduler is not None
        scheduler = self.scheduler
        if scheduler is None:
            scheduler = (os.environ.get("REPRO_SCHEDULER", "")
                         or ("cb" if kv_layout == "paged" else "fifo"))
        if scheduler not in ("fifo", "cb"):
            raise ValueError(
                f"scheduler must be 'fifo' or 'cb', got {scheduler!r} "
                "(check REPRO_SCHEDULER)")
        if scheduler == "cb" and kv_layout != "paged":
            if explicit_sched:
                raise ValueError(
                    "scheduler='cb' needs kv_layout='paged' — preemption "
                    "offloads KV pages and the dense layout has none")
            scheduler = "fifo"

        # two-tier pool knobs (paged only): host_pages bounds the host
        # offload tier, prefix_cache_pages bounds the cached-free prefix
        # index. Same explicit-raise / env-degrade contract.
        env_host = os.environ.get("REPRO_HOST_PAGES", "")
        env_cache = os.environ.get("REPRO_PREFIX_CACHE_PAGES", "")
        explicit_tier = (self.host_pages is not None
                         or self.prefix_cache_pages is not None)
        host_pages = self.host_pages
        prefix_cache_pages = self.prefix_cache_pages
        if host_pages is None and env_host:
            host_pages = int(env_host)
        if prefix_cache_pages is None and env_cache:
            prefix_cache_pages = int(env_cache)
        if kv_layout != "paged" and (host_pages is not None
                                     or prefix_cache_pages is not None):
            if explicit_tier:
                raise ValueError(
                    "host_pages / prefix_cache_pages need "
                    "kv_layout='paged' — the dense layout has no page pool")
            host_pages = prefix_cache_pages = None

        # chunked prefill (paged only; the dense layout prefills whole
        # prompts and ignores the knob, matching the old kwarg behavior)
        prefill_chunk = self.prefill_chunk
        if kv_layout == "paged":
            prefill_chunk = (prefill_chunk
                             or int(os.environ.get("REPRO_PREFILL_CHUNK",
                                                   0))
                             or DEFAULT_PREFILL_CHUNK)
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk} "
                    "(check REPRO_PREFILL_CHUNK)")

        # tensor-parallel width. Same explicit-raise / env-degrade
        # contract: REPRO_SHARDS on a dense engine degrades to 1, an
        # explicit shards= there is a caller error (the sharded engine
        # partitions the *paged* KV/state pools over the model axis).
        explicit_shards = self.shards is not None
        shards = self.shards
        if shards is None:
            shards = int(os.environ.get("REPRO_SHARDS", "1") or 1)
        if shards < 1:
            raise ValueError(f"shards must be >= 1, got {shards} "
                             "(check REPRO_SHARDS)")
        if shards > 1 and kv_layout != "paged":
            if explicit_shards:
                raise ValueError(
                    f"shards={shards} needs kv_layout='paged' — the "
                    "sharded engine head-shards the paged KV/state pools "
                    "over the model axis")
            shards = 1

        replicas = self.replicas
        if replicas is None:
            replicas = int(os.environ.get("REPRO_REPLICAS", "1") or 1)
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas} "
                             "(check REPRO_REPLICAS)")

        return dataclasses.replace(
            self, kv_layout=kv_layout,
            kv_cache_dtype=jnp.dtype(self.kv_cache_dtype),
            prefix_cache=bool(prefix_cache), spec_decode=bool(spec_decode),
            spec_k=spec_k, fused_decode=True,
            scheduler=scheduler, host_pages=host_pages,
            prefix_cache_pages=prefix_cache_pages,
            prefill_chunk=prefill_chunk, shards=shards, replicas=replicas,
            resolved=True)
