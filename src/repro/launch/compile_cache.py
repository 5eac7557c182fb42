"""Persistent compilation cache for the entry points (never set at import).

JAX keys cache entries by, among other things, the cache directory, so the
directory must not move between runs: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads it itself, and nothing else is set), otherwise
``.jax_cache`` at the root of the checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
