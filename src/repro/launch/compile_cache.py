"""Where JAX keeps its persistent compilation cache.

Called by entry points (``chip_smoke.py``, ``repro.launch.train``) before
their first compile — never when a library module is imported.  A cold
compile of a full-width train step takes minutes on the chip; the cache
lets the second and later programs of one run, and later runs from the
same checkout, load it instead.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: The cache directory when ``ENV`` is unset: fixed, inside the checkout
#: (the path is part of the cache key, so it must not move between runs).
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def configure_compile_cache() -> str:
    """Point JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR`` when
    set (JAX reads the variable itself, so nothing is set here), else at
    :data:`DEFAULT_DIR`.  Returns the directory in use."""
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
