"""JAX's persistent compilation cache for the entry points.

Called once from each script's ``__main__`` (serve, train, chip_smoke),
never on import, so tests and library callers keep the cache off. The
directory is part of what a cache entry is found by, so it is fixed: it
never holds a temporary name, a process id or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# <checkout>/.jax_cache (gitignored): this file is src/repro/launch/
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache goes to
    ``<checkout>/.jax_cache``."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
