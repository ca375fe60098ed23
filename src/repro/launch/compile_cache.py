"""Persistent XLA compilation cache for the programs that drive the chip.

A cold grappa-90k run compiles every block program, which takes minutes;
the cache lets later processes of the same checkout skip that.  Entry
points call :func:`enable_compile_cache` once at start-up (never at
import, so the tests stay cache-free).
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as it is: JAX
    reads it itself and nothing else is set here.  Otherwise the cache
    lives at ``<checkout>/.jax_cache`` (a fixed path, because the path
    is part of the cache key).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
