"""JAX's persistent compilation cache, at one fixed place per checkout.

A cold process on the chip otherwise recompiles every program. The cache key
includes the directory, so the path never holds a temporary name, a process
id or a time: a second run from the same checkout finds what the first one
compiled.
"""
from __future__ import annotations

import os

import jax

# <checkout>/artifacts/jax_cache (artifacts/ is git-ignored)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    "artifacts", "jax_cache",
)


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory: ``JAX_COMPILATION_CACHE_DIR``
    where that is set (JAX reads it itself; no other directory is set),
    otherwise :data:`DEFAULT_DIR`."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
