"""JAX's persistent compilation cache, placed by the entry points.

Called from an entry point's `main()`, never at import: importing the
package must not change JAX's configuration for a caller (tests, a
notebook) that placed the cache itself or wants none.
"""

from __future__ import annotations

import os

import jax

#: the checkout root (this file is src/repro/launch/jax_cache.py)
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Keep compiled programs across processes; returns the directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it itself and
    nothing else is set. Otherwise the cache is `.jax_cache/` at the
    checkout root: a fixed path, because the path is part of an
    entry's key and a cache that moves never hits."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
