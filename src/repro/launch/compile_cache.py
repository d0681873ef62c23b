"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` first, before anything is
compiled.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing here touches the setting.  Otherwise the cache goes
to one fixed directory inside the checkout (``.jax_cache/`` at the
repository root, listed in ``.gitignore``): a fixed path is what lets a
later process find the entries an earlier one wrote.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at its directory; returns it."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
