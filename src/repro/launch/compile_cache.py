"""JAX's persistent compilation cache, placed from outside the program.

Entry points (``launch/serve.py``, ``chip_smoke.py``, ``benchmarks/run.py``)
call :func:`enable_compile_cache` before their first compile; library code
never does. With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and
nothing else is set. Otherwise the cache lives at one fixed path in the
checkout, ``.jax_cache/`` (git-ignored): the directory is part of what a
cached program is found by, so it is never derived from a temp name, a pid
or the time.
"""
from __future__ import annotations

import os

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
