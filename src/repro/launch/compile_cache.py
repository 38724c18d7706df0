"""JAX's persistent compile cache for the entry points.

A cold start compiles every (B, S) bucket of every tier; with the cache on,
a later run from the same checkout loads them instead.  The cache is keyed
by its directory too, so the directory never moves between runs.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is the directory: JAX reads
    it itself and no other is set here.  Otherwise the cache lives at
    ``DEFAULT_DIR`` inside the checkout.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
