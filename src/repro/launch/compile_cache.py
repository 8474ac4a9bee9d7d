"""Persistent XLA compilation cache for the entry points.

The launchers call :func:`enable_compile_cache` before their first compile
(never on ``import repro``, never in tests).  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing else
is configured; otherwise the cache lives at the fixed ``<repo>/.jax_cache``
(the directory is part of the cache key, so it must not move).
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(REPO_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
