"""Kernel registry: one dispatch table instead of per-callsite string checks.

Kernels register ``(op, impl)`` pairs with an availability predicate and a
priority; resolution happens **once** per (op, impl, backend) and is cached,
so the nn/ layers never re-derive "pallas on TPU, ref elsewhere" themselves.

  register(op, impl, priority=..., available=...)   — decorator
  resolve(op, impl="auto") -> KernelEntry           — cached resolution
  available_impls(op) -> tuple[str, ...]

``impl`` semantics (unchanged from the old kernels/ops.py dispatch):
  * "auto"      — highest-priority available impl (pallas on TPU, ref
                  elsewhere: pallas registers with a TPU-only predicate)
  * "pallas"    — compiled Mosaic kernel (TPU target)
  * "interpret" — pallas_call(interpret=True); tests validate the kernel
                  body bit-for-bit against the ref oracle on CPU
  * "ref"       — pure-jnp oracle

The built-in kernels live in ``repro.kernels.ops`` and register themselves
at import; ``resolve`` imports that module lazily so the registry package
itself stays dependency-free. Current built-in ops: ``spx_matmul``,
``spx_expert_matmul`` (the same over a stack of experts, rows grouped by
expert — the serving MoE layer), ``flash_attention``, ``paged_attention`` (serving decode over the paged KV
cache — see docs/SERVING.md), ``paged_attention_quant`` (same, over
codes+scale quantized pools with fused codebook dequant —
docs/QUANTIZATION.md), and ``paged_decode_ragged`` /
``paged_decode_ragged_quant`` (the decode megakernel: one launch per
serving decode tick over a ragged (slot, attend_len) grid, covering plain
decode and the speculative verify window, with in-kernel LUT dequant for
quantized pools), and ``paged_kv_write`` (a step's new K/V rows into the
stacked page pools, in place).
"""
from __future__ import annotations

import dataclasses
import functools
import importlib
from typing import Callable

__all__ = ["KernelEntry", "KernelUnavailable", "register", "resolve",
           "available_impls", "registered_ops", "PRIORITY_ACCELERATOR",
           "PRIORITY_REFERENCE", "PRIORITY_DEBUG"]

#: priority tiers for "auto" resolution (highest available wins; explicitly
#: requested impls bypass priority entirely). Registrations should use
#: these rather than raw ints so the ordering lives in one place.
PRIORITY_ACCELERATOR = 100      # compiled device kernel (pallas)
PRIORITY_REFERENCE = 10         # pure-jnp oracle
PRIORITY_DEBUG = 1              # interpret-mode kernel (slow, CPU)


class KernelUnavailable(LookupError):
    pass


@dataclasses.dataclass(frozen=True)
class KernelEntry:
    op: str
    impl: str
    fn: Callable
    available: Callable[[], bool]
    priority: int


_REGISTRY: dict[tuple[str, str], KernelEntry] = {}
_BUILTINS_LOADED = False


def register(op: str, impl: str, *, priority: int = 0,
             available: Callable[[], bool] = lambda: True):
    """Decorator: register ``fn`` as the ``impl`` implementation of ``op``.

    ``available`` is evaluated at resolve time (per backend), not at import:
    the pallas entries register everywhere but only resolve on TPU.
    Registering invalidates the resolution cache, so a late registration
    (e.g. a test stubbing an op) takes effect on the next ``resolve``.
    ``priority`` only orders ``"auto"`` resolution — use the
    ``PRIORITY_*`` tiers above rather than raw ints.
    """
    def deco(fn):
        _REGISTRY[(op, impl)] = KernelEntry(op, impl, fn, available, priority)
        _resolve_cached.cache_clear()
        return fn
    return deco


def _ensure_builtins():
    global _BUILTINS_LOADED
    if not _BUILTINS_LOADED:
        importlib.import_module("repro.kernels.ops")
        # only latch on success so a transient import failure surfaces on
        # every call instead of decaying into "no impl registered"
        _BUILTINS_LOADED = True


def _backend() -> str:
    import jax
    return jax.default_backend()


@functools.lru_cache(maxsize=None)
def _resolve_cached(op: str, impl: str, backend: str) -> KernelEntry:
    del backend  # part of the cache key: availability is backend-dependent
    if impl != "auto":
        entry = _REGISTRY.get((op, impl))
        if entry is None:
            raise KernelUnavailable(
                f"no impl {impl!r} registered for op {op!r}; "
                f"have {available_impls(op)}")
        return entry
    candidates = [e for (o, _), e in _REGISTRY.items()
                  if o == op and e.available()]
    if not candidates:
        raise KernelUnavailable(f"no available impl for op {op!r}")
    return max(candidates, key=lambda e: e.priority)


def resolve(op: str, impl: str = "auto") -> KernelEntry:
    """Resolve ``(op, impl)`` to a registered entry.

    Cached per (op, impl, backend) for the process lifetime — availability
    predicates run once per backend, not per call, so layers may resolve
    inside jitted code at zero cost. Raises ``KernelUnavailable`` for an
    unknown impl (listing what exists) or when no registered impl's
    availability predicate passes for ``"auto"``.
    """
    _ensure_builtins()
    return _resolve_cached(op, impl, _backend())


def available_impls(op: str) -> tuple[str, ...]:
    """Impl names whose availability predicate passes right now, sorted.
    Uncached — predicates are re-evaluated on every call (cheap; used for
    error messages and diagnostics, not on hot paths)."""
    _ensure_builtins()
    return tuple(sorted(i for (o, i), e in _REGISTRY.items()
                        if o == op and e.available()))


def registered_ops() -> tuple[str, ...]:
    """All op names with at least one registered impl (available or not),
    sorted. Uncached."""
    _ensure_builtins()
    return tuple(sorted({o for (o, _) in _REGISTRY}))
