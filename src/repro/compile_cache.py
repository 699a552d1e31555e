"""JAX's persistent compilation cache, for the runnable entry points.

Entry points (``chip_smoke.py``, ``examples/*.py``, ``benchmarks/run.py``)
call :func:`use_compile_cache` once before compiling anything; importing the
library never touches the cache. Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and nothing else is configured. Otherwise the cache lives
at one fixed path inside the checkout, ``<repo>/.jax_cache`` (git-ignored):
the directory is part of each entry's key, so a path that moved between runs
would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Enable the persistent compilation cache; return its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
