"""Where JAX keeps its persistent compile cache for this checkout.

The chip entry points (``chip_smoke.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` at start-up. The cache key includes its
directory, so the directory is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that variable itself, and nothing is set
here), otherwise ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: ``<checkout>/.jax_cache`` — this file sits at ``<checkout>/src/repro/``
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    """Point JAX's persistent compile cache at a stable directory."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
