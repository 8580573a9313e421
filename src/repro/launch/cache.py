"""Persistent compilation cache for the entry points.

``chip_smoke.py``, ``launch.serve`` and ``launch.train`` call
:func:`enable_compile_cache` once at start-up.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing
else is configured.  Otherwise the cache lives at ``.jax_cache/`` in
the checkout: a fixed path, because the path is part of the cache key
and a directory that moves never hits.  Tests never call this.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
