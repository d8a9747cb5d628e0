"""JAX's persistent compilation cache, at one fixed place per checkout.

Every entry point (``launch/serve.py``, ``launch/train.py``,
``benchmarks/run.py``, ``chip_smoke.py``) calls ``enable_compile_cache``
before its first compile, so processes of one checkout reuse each other's
executables.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (jax reads it
    itself; nothing else is configured).  Otherwise the cache is
    ``<checkout>/.jax_cache``: a fixed path, because the directory is part
    of what makes a later process find an entry again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
