"""JAX's persistent compilation cache for the launchers and the chip smoke.

The cache key includes the cache directory, so the directory must not move
between runs: where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads
it and nothing here overrides it; otherwise the cache lives at one fixed
path inside the checkout (``<repo>/.jax_cache``, listed in ``.gitignore``).
Entry points call :func:`enable_compile_cache` from their ``main()``;
importing a ``repro`` module never turns the cache on, and tests never do.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; return the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
