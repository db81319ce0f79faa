"""Persistent XLA compilation cache for the entry points.

The cache's path is part of its key, so it lives at one fixed place: the
directory ``JAX_COMPILATION_CACHE_DIR`` names when it is set (JAX reads
that variable itself, and nothing here overrides it), otherwise
``<checkout>/.jax_cache``. Entry points call ``enable_compile_cache()``
first thing in ``main()``; importing this module changes nothing.
"""
from __future__ import annotations

import os
import pathlib

__all__ = ["enable_compile_cache", "DEFAULT_CACHE_DIR"]

#: <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
