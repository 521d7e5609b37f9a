"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`use_compile_cache` before their first compile.
A directory named by ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting
and is left alone.  Otherwise the cache goes to ``.jax_cache/`` at the
root of the checkout: a fixed path, because the path is part of what a
later run must find again.  Tests do not call this.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> Path:
    """Turn the persistent compile cache on; returns its directory."""
    if os.environ.get(ENV):
        return Path(os.environ[ENV])
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return DEFAULT_DIR
