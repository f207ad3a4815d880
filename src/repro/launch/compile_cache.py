"""JAX's persistent compilation cache, kept where the next run finds it.

Every entry point that runs on the chip (``chip_smoke.py``,
``benchmarks/run.py``) calls ``enable_compile_cache`` before its first
compile.  JAX reads a cache entry back only from the directory it was
written to, so that directory never moves between runs:

* ``JAX_COMPILATION_CACHE_DIR`` when it is set — JAX reads the variable
  itself, and nothing here overrides it;
* otherwise ``.jax_cache/`` at the root of the checkout (listed in
  ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory;
    returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
