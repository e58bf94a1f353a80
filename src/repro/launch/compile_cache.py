"""JAX's persistent compile cache for the serving entry points.

A 28-layer window program takes minutes to compile from cold. The
persistent cache lets a later process on the same machine find it again.
Its directory is part of what makes an entry found, so it is fixed: the
one ``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself;
nothing else is set here), or else ``.jax_cache/`` at the root of the
checkout, which git ignores.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; call before the first
    compile. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
