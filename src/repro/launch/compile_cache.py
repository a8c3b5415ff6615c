"""Persistent XLA compilation cache for the command-line entry points.

``enable_compile_cache()`` is called at the start of each ``main()``
(``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``), never
at import. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it
and nothing is changed. Otherwise the cache goes to ``.jax_cache`` at the
root of the checkout: a fixed path, because the directory is part of the
cache key, so a per-run name would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory; returns it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
