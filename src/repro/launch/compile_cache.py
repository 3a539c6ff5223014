"""Where JAX keeps its persistent compilation cache.

Called once at the start of ``chip_smoke.py`` and of every launcher,
before anything compiles.  When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and nothing is set here.  Otherwise the cache goes to
``.jax_cache/`` at the root of the checkout: a fixed path, so a second
run of the same program finds what the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache", "DEFAULT_DIR"]

#: ``<checkout>/.jax_cache`` (this file is src/repro/launch/compile_cache.py)
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
