"""Where JAX keeps its persistent compilation cache.

Entry points that compile the served model (``repro.launch.serve``,
``chip_smoke.py``) call :func:`enable_compile_cache` before their
first compile. When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here. Otherwise the cache lives at one fixed
path inside the checkout, ``<checkout>/.jax_cache`` (gitignored): the
path is part of the cache key, so a directory named after a temporary
file, a process id or the time would never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns it."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
