"""Forced host device counts, handled in one place.

``--xla_force_host_platform_device_count=N`` makes the CPU backend expose
N fake devices — how every multi-device path here (dry-runs, sharded
serving tests, sharded benches) runs on one CPU. The flag only works if
it is in ``XLA_FLAGS`` *before* jax initializes its backend, which makes
it exactly the kind of global a module must not set at import time:
PR 6's ``launch/dryrun.py`` did, and every process that imported anything
from it inherited 512 fake devices (benchmarks/roofline.py grew a lazy
import to dodge that).

This module is the shared helper instead — import-safe (never touches
jax): ``set_host_device_count(n)`` mutates THIS process's ``XLA_FLAGS``.
Call it at the top of a ``main()``, before anything runs a jax
computation. It replaces an existing force flag rather than stacking a
second one and preserves unrelated flags. A process that holds a chip
spawns no second jax process: the chip belongs to one process at a time.
"""
from __future__ import annotations

import os
import re

__all__ = ["set_host_device_count"]

_FLAG = "--xla_force_host_platform_device_count"
_FLAG_RE = re.compile(re.escape(_FLAG) + r"=(\d+)")


def _with_flag(flags: str, n: int) -> str:
    """``flags`` with the force flag set to ``n`` (replacing any existing
    occurrence, keeping every other flag)."""
    if _FLAG_RE.search(flags):
        return _FLAG_RE.sub(f"{_FLAG}={n}", flags)
    return f"{flags} {_FLAG}={n}".strip()


def set_host_device_count(n: int) -> None:
    """Force ``n`` host devices for THIS process. Only effective before
    jax initializes its backend — call it first thing in a ``main()``,
    never at module import."""
    if n < 1:
        raise ValueError(f"host device count must be >= 1, got {n}")
    os.environ["XLA_FLAGS"] = _with_flag(os.environ.get("XLA_FLAGS", ""), n)
