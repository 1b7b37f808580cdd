"""Persistent XLA compilation cache for the entry points.

A cold start on the chip compiles every search, build and serving program
from scratch.  :func:`enable` keeps compiled programs on disk so the next
process with the same programs loads them instead:

* if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it, and the
  cache lives there — this module sets no other directory;
* otherwise the cache lives in ``.jax_cache/`` at the root of the
  checkout, a fixed path (the path is part of the cache key, so a
  temporary or per-process directory would never hit).

Entry points call it (``chip_smoke.py``, ``launch/serve.py``,
``launch/build_index.py``, ``benchmarks/run.py``); tests and imports never
do.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: the in-checkout default: <repo>/.jax_cache (listed in .gitignore)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # cache every program: the search loop's many small programs each cost
    # a compile on a cold start, not only the ones over the 1 s default
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
