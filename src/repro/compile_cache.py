"""JAX's persistent compilation cache for the repo's entry points.

Scripts, benchmarks and examples call :func:`enable_compile_cache` before
they compile; importing the library does not, so tests run without a
cache.
"""

from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache"]

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is honored as JAX reads it
    and nothing overrides it; otherwise the cache lives in
    ``<checkout>/.jax_cache``.  A fixed path matters: it is part of the
    cache key, so a directory that moves never hits.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
