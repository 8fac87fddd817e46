"""Where the command-line entry points keep JAX's compilation cache.

A cold chip run recompiles every program; the persistent cache lets
the processes of one run, and later runs on the same disk, reuse them.
Call ``enable_compile_cache`` from a ``main()``, never at import, so
library users and tests keep JAX's default (no cache).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here. Otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout (git-ignored). The path is fixed: a
    directory that moves from run to run never hits.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
