"""JAX's persistent compilation cache, at one fixed place per checkout.

Every entry point that compiles on the chip (``chip_smoke.py``,
``repro.launch.serve``, ``repro.launch.train``) calls
:func:`use_compile_cache` before its first compile.  Where
``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and this sets
nothing.  Otherwise the cache goes to ``.jax_cache/`` at the root of the
checkout: the directory is part of the cache key, so it never holds a
temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point jax's compilation cache at its directory; returns the path."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
