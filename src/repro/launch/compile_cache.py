"""Where the entry points keep JAX's persistent compilation cache.

A run on a fresh machine compiles every program from scratch; the cache
lets a second process, or a second run on a machine that keeps its disk,
load them instead. Entry points call `enable_compile_cache()` before
their first compile. Importing this module changes nothing.
"""
from __future__ import annotations

import os
from pathlib import Path

# src/repro/launch/compile_cache.py -> the repository root
REPO_ROOT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    When `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and this
    sets no other path. Otherwise the cache goes to `<repo root>/.jax_cache`,
    fixed by this file's location: a later run can only hit a cache it
    finds at the same path, so the path never holds a temporary name, a
    pid or a time."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
