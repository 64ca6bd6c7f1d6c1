"""Where the entry points keep JAX's persistent compilation cache.

The cache is keyed on its own path, so it lives at one fixed place:
``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself, so nothing is set here), otherwise ``.jax_cache``
at the root of the checkout. Call ``use_compile_cache()`` before the
first compile; tests do not call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
