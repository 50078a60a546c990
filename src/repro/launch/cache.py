"""Where the launch entry points keep JAX's persistent compilation cache.

Called from each entry point's ``main`` (never at import, never from tests):
a described-chip compile written to the cache cannot be read back without
the chip, so a test run must not turn the cache on.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed path inside the checkout; the path is part of the cache key, so it
#: must not move between runs (no temp name, pid or time in it)
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already caches there and
    nothing is changed. Otherwise the cache goes to ``<repo>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
