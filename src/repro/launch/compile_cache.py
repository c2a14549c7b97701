"""JAX's persistent compilation cache, set in one place.

Called by the launchers and ``chip_smoke.py``, never on import, so tests
and library users never start writing a cache. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and nothing is
set here. Otherwise the cache lives at one fixed path inside the checkout
(listed in ``.gitignore``): the directory is part of what a cached entry is
found by, so a path that moved between runs would never hit.
"""
from __future__ import annotations

import os

import jax

CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def configure_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
