"""Where JAX keeps its persistent compilation cache for this repository.

A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and is left as it
is.  Otherwise the entry points (``chip_smoke.py``, ``benchmarks/run.py``,
the examples) keep the cache at one fixed path inside the checkout,
``<repo>/.jax_cache``: the path is part of the cache key, so a directory
that moved between runs would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
