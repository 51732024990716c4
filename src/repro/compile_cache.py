"""Where JAX keeps its persistent compilation cache.

Compiled programs are cached on disk so a second run of the same program
skips XLA and Mosaic.  A cache only hits when its directory is the same
on every run, so the path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when
it is set (JAX reads that variable itself), otherwise ``.jax_cache/`` at
the root of this checkout.  Entry points (``chip_smoke.py``,
``python -m repro.serve``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` before their first compile.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
