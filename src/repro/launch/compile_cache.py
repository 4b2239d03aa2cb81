"""Persistent XLA compile cache for the entry points.

Called from ``main()`` of the launchers, the benchmark scripts and
``chip_smoke.py`` — never when a library module is imported.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
here overrides it.  Otherwise the cache lives at ``.jax_cache/`` in the
checkout root: a fixed path, since the path is part of what makes a
second run find the first run's entries.
"""
from __future__ import annotations

import os

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory; returns it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
