"""Where the launchers keep JAX's persistent compilation cache.

A compile that the cache holds is not repeated by the next process that
runs the same program, which matters most on a chip host, where one whole
model step can take a minute to compile.  The cache is keyed by its path,
so the directory must not move between runs.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CHECKOUT_ROOT", "use_compile_cache"]

CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache goes to ``.jax_cache/`` at the
    checkout root.  Called by entry points, never at import time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
