"""Persistent XLA compilation cache for the entry points.

A cold process recompiles every program (a 28-layer model's prefill,
decode and merge steps among them); the persistent cache lets a second
process, or a second engine in the same process, load identical programs
instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

# fixed and inside the checkout: a run writes nothing outside it, and the
# next run from the same checkout finds what this one compiled
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the cache on and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here; otherwise the cache lives in ``.jax_cache/`` at
    the root of the checkout."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
