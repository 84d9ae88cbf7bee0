"""Where JAX keeps its persistent compilation cache.

Every entry point (``launch/train.py``, ``launch/serve.py``,
``chip_smoke.py``) calls ``enable_compile_cache()`` before it compiles
anything.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that
directory itself and this sets nothing.  Otherwise the cache goes to
``<checkout>/.jax_cache``, a path derived from this package's location:
the cache key includes nothing that moves, so a fixed directory lets a
second process of the same checkout reuse the first one's executables.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
