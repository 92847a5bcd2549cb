"""Persistent compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``repro.launch.serve``, ``repro.launch.train``)
call :func:`enable_compile_cache` once at start-up; importing ``repro`` never
touches the cache.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and no other directory is set here.  Otherwise the cache lives at a
fixed path inside the checkout, ``<repo>/.jax_cache``: the directory is part
of the cache key, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``<repo>/.jax_cache`` for the src layout (``<repo>/src/repro/launch/``).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
