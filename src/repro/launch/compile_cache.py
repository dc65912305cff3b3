"""JAX persistent compilation cache for the entry points.

``enable_compile_cache()`` is called by ``repro.launch.serve``,
``repro.launch.train``, ``benchmarks/run.py`` and ``chip_smoke.py`` as they
start, never at import (tests compile in-process and keep no cache).

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
is set here. Otherwise the cache lives in ``<repo>/.jax_cache`` (listed in
``.gitignore``). The directory is fixed: a later process finds an entry
only at the path it was written to, so it is never derived from a
temporary directory, a pid or a time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parents[3]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
