"""Persistent XLA compile cache for the program's entry points.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax already reads it and
this module sets no other directory. Otherwise the cache lives at one
fixed path inside the checkout (``.jax_cache/``, gitignored): the path is
part of the cache key, so a directory that moves never hits.

Entry points (``chip_smoke.py``, the query service's ``main``, the
examples and benchmark CLIs) call :func:`enable_compile_cache` once,
before their first compile. Library code and tests never do.
"""

from __future__ import annotations

import os
import pathlib

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    # the device collectives compile in well under jax's default 1 s
    # threshold; cache every program so a repeated run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
