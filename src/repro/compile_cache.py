"""Where JAX keeps its persistent compilation cache.

A cold compile of a full-width served step takes seconds per program, and
the cache key includes the cache's path, so the directory must not move
between runs. ``JAX_COMPILATION_CACHE_DIR``, when set, wins: JAX reads it
itself and nothing here touches it. Otherwise the cache lives at the
fixed ``<repo root>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory. Call before the first compile."""
    if os.environ.get(ENV):
        return os.environ[ENV]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
