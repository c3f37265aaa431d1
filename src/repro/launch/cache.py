"""Where JAX keeps its persistent compilation cache.

A cold 32-layer serve run compiles every program; the persistent cache lets
a later process on the same machine load them instead. The cache is keyed on
its directory, so the directory is fixed: ``JAX_COMPILATION_CACHE_DIR`` when
it is set (JAX reads the variable itself, and nothing else is set here), else
``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "use_persistent_compile_cache"]

CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def use_persistent_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
