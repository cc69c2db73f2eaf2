"""Where JAX keeps its persistent compilation cache.

The cache key includes the cache's path, so the directory must not move
between runs: it is either the one ``JAX_COMPILATION_CACHE_DIR`` names,
which JAX reads by itself, or a fixed directory inside the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Call before the first compilation. Where ``JAX_COMPILATION_CACHE_DIR``
    is set, nothing is configured here.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
