"""Persistent XLA compilation cache for the launch entry points.

Called by the train and serve CLIs and by ``chip_smoke.py`` — never at
package import, so tests and library callers write no cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache.  Fixed and derived from this file's location: the
# directory is part of what a later run must find again, so it never comes
# from a temp name, a pid or the clock.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and no
    other directory is set here; otherwise the cache lives in
    :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
