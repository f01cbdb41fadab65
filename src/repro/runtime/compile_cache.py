"""JAX's persistent compilation cache, placed from outside.

``JAX_COMPILATION_CACHE_DIR``, when set, places the cache: JAX reads the
variable itself, so nothing is set in code.  Otherwise the cache lives at
the fixed ``<repo>/.jax_cache`` (gitignored).  The path is part of the
cache key, so it never comes from a temp name, a pid or the clock.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
