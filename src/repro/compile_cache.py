"""Persistent JAX compilation cache for the repository's entry points.

`chip_smoke.py`, `examples/*.py` and `benchmarks/run.py` call
`enable_compile_cache()` once, before their first compile. Importing
`repro` never does, so the tests compile exactly as before.
"""
from __future__ import annotations

import os
from pathlib import Path

# One fixed directory inside the checkout: the cache key includes the
# path, so a directory that moved between runs would never hit.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX already reads it and
    this sets no other location. Otherwise the cache goes to
    `<checkout>/.jax_cache`, which `.gitignore` lists."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
