"""Where JAX keeps its persistent compilation cache.

A full-width serving program takes tens of seconds to compile, once per
shape bucket, so every entry point keeps compiled programs across runs.
The cache directory is part of each entry's key, so it must not move
between runs: it comes from ``JAX_COMPILATION_CACHE_DIR`` when that is
set (JAX reads the variable itself), and is otherwise a fixed directory
inside the checkout.
"""
from __future__ import annotations

import os
import pathlib

import jax

# <repo>/src/repro/runtime/compile_cache.py -> <repo>/.jax_cache
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns it.
    Call from an entry point's ``main()``, before anything compiles."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
