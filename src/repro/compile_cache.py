"""Persistent XLA compile cache for the entry points.

Entry points (``chip_smoke.py``, ``examples/wireless_fl.py``,
``benchmarks/run.py``) call ``enable_compile_cache()`` once at start-up;
library modules never do.  The cache key includes the directory, so the
directory is fixed: it never comes from a temporary name, a process id
or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# <checkout>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and nothing else is set; otherwise the cache lives in
    ``DEFAULT_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
