"""Where JAX keeps its persistent compilation cache.

A fixed directory lets a later process find the programs an earlier one
compiled; the path is part of what the cache matches on, so it never comes
from a temporary name, a process id or the clock.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# the checkout's root: src/repro/compile_cache.py -> ../..
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed.  Otherwise the cache goes to ``.jax_cache/`` at the
    root of the checkout.  Call it from an entry point before the first
    compile, never at import.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
