"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``launch/serve.py``, ``benchmarks/run.py``,
``chip_smoke.py``) call ``enable()`` once, before their first compile:

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the directory from
  the environment and nothing here names another one;
* without it, the cache lives at one fixed path inside the checkout,
  ``<repo>/.jax_cache`` (listed in ``.gitignore``), so every run from
  the same checkout finds what earlier runs compiled.

Every executable is cached, however quickly it compiled: the engine
builds many small per-layer executables, each under JAX's default
one-second threshold, and together they are most of a cold start.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    if not os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
