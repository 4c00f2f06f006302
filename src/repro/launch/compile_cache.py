"""JAX's persistent compilation cache for the launchers.

``enable()`` is called by the entry points that compile full-size
programs (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``),
never at import and never from tests.  Where ``JAX_COMPILATION_CACHE_DIR``
is set JAX reads it itself and nothing is set here; otherwise the cache
lives at a fixed ``.jax_cache/`` in the repository root (the path is part
of the cache key, so it must not move between runs).
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
