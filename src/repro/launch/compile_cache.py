"""Persistent XLA compilation cache for the chip entry points.

JAX keys a cached executable by, among other things, the cache directory,
so the directory must not move between runs: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX reads it itself and nothing here
overrides it; otherwise the cache lives at the fixed ``.jax_cache`` at the
root of the checkout (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
