"""JAX persistent compilation cache at a fixed, reusable path.

The cache key includes the directory, so a temporary or per-process path
never hits. `use_compile_cache()` leaves JAX's own choice alone when
`JAX_COMPILATION_CACHE_DIR` is set (JAX reads that variable itself) and
otherwise keeps the cache in `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import os

import jax

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def use_compile_cache() -> str:
    """Switch the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
