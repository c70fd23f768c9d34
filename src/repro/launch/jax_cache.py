"""Persistent JAX compilation cache for the launchers.

``setup_compile_cache()`` is called at the top of each launcher's ``main``
(never at import). When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here, so the cache can be placed from outside.
Otherwise the cache lives in ``.jax_cache/`` at the root of the checkout,
derived from this file's location, so repeated runs from one checkout find
their compiled programs again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    if os.environ.get(ENV_VAR):
        return os.environ[ENV_VAR]
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
