"""Where JAX keeps its persistent compilation cache.

A cache entry is found again only under the directory it was written to,
so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself), otherwise
``.jax_cache/`` at the root of the checkout, which git ignores.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Call before the first compilation of the process: JAX opens the
    cache once.  When ``JAX_COMPILATION_CACHE_DIR`` is set this sets
    nothing, so that directory is the only one used.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
