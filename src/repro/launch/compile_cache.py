"""Persistent XLA compilation cache for the entry points (``serve.run``,
``chip_smoke.py``). Nothing here runs at import time."""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout, so that the next run finds what this one
# wrote: a temp-, pid- or time-based path would start empty every run.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at a directory; return it.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set in code. Otherwise the cache goes to ``.jax_cache/`` at
    the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
