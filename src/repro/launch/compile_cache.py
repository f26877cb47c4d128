"""Where JAX keeps its persistent compilation cache.

A compiled program is cached under a key that includes the cache's own
path, so the directory must not move between runs: it is either the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself) or one
fixed directory inside the checkout, ``<repo>/.jax_cache`` (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: The checkout-local cache directory used when the environment names none.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set this configures nothing — JAX
    already caches there.  Otherwise it points ``jax_compilation_cache_dir``
    at :data:`REPO_CACHE_DIR`.  Touches no device, so it is safe to call
    before ``jax.distributed.initialize()``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
