"""Where JAX keeps its persistent compilation cache for entry points.

Called by entry points (``chip_smoke.py``, ``repro.launch.serve``) before
their first compile — never at import time.  JAX keys cache entries by
the cache path among other things, so the default path is fixed: a
directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets nothing.  Otherwise the cache goes to ``.jax_cache`` at the
    root of the checkout (listed in ``.gitignore``)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
