"""Where JAX's persistent compilation cache lives.

Entry points call :func:`use_compile_cache` at start-up, never at import.
The cache key includes its directory, so the directory is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself), or
else ``.jax_cache/`` at the checkout root — never a temporary, per-process
or time-stamped path, which would never hit."""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
