"""JAX's persistent compilation cache, in one fixed place.

Every entry point (``chip_smoke.py``, ``launch/serve.py``,
``launch/train.py``, ``benchmarks/run.py``) calls ``enable_compile_cache``
before its first compile.  When ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX reads it itself and no other directory is set here.  Otherwise the
cache lives at ``<repo>/.jax_cache`` (gitignored): a fixed path, never
derived from a temporary name, a process id or the time, because the
path is part of what a later run must find again.
"""
from __future__ import annotations

import os

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process; returns its
    directory.  Every compiled program is cached, however quick its
    compile was (a cold search path is dozens of small programs)."""
    path = os.environ.get(CACHE_ENV) or DEFAULT_CACHE_DIR
    if not os.environ.get(CACHE_ENV):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
