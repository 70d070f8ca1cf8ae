"""Persistent compilation cache location shared by every entry point."""
from __future__ import annotations

import os

import jax

#: the checkout root (src/repro/launch/cache.py -> three levels up)
REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    left alone.  Otherwise the cache lives at ``<checkout>/.jax_cache``:
    the path is part of what a cache hit matches, so it is fixed, never
    derived from a temporary name, a process id or the time.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
