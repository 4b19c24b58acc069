"""Where JAX keeps compiled programs between runs of an entry point.

Entry points (``chip_smoke.py``, ``benchmarks/chip/bench.py``) call
:func:`use_compile_cache` before their first compile; importing the
library never does, so tests and library callers keep whatever cache
policy their process already has.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the fixed fallback location: a cache whose directory moves between
#: runs never hits, so the path is never built from a temporary name, a
#: pid or the time
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn JAX's persistent compilation cache on and return its
    directory.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing else is set here; otherwise the cache lives in
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
