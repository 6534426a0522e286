"""Persistent XLA compilation cache for the entry points.

``chip_smoke.py``, the ``benchmarks`` mains and the ``examples`` mains
call ``enable_compile_cache()`` first thing; importing a module never
touches the cache.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own
setting and wins: nothing is configured in code.  Otherwise the cache
lives at a fixed path inside the checkout (``<repo>/.jax_cache``, listed
in ``.gitignore``) — never a temp name, pid or time — so a second run of
any entry point finds what the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    # JAX skips programs that compile in under a second by default; the
    # serving path is many small per-k, per-bucket programs, so keep all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return str(CACHE_DIR)
