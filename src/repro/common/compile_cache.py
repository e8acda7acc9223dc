"""Persistent XLA compile cache for the entry points (never on import)."""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory.

    A directory placed from outside (``JAX_COMPILATION_CACHE_DIR``, which
    JAX reads itself) wins and nothing is set here; otherwise the cache
    lives at ``<repo>/.jax_cache``.  The path is part of the cache key, so
    it must not move between runs.  Returns the directory in use.
    """
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    path = str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
