"""JAX persistent compilation cache, placed from outside or at a fixed path.

Every entry point (``chip_smoke.py``, ``launch/train.py``, the
``benchmarks/`` mains) calls ``enable_compile_cache()`` once at start-up,
so a second run of the same program on the chip loads its executables
instead of compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here.
* otherwise: the cache lives in ``.jax_cache/`` at the root of the
  checkout (listed in ``.gitignore``). The path is fixed — no temp name,
  pid or time — because the directory is part of where a later run looks.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
