"""Persistent compilation cache for the entry points.

Entry points (``launch/serve.py`` as a script, ``chip_smoke.py`` and
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile, so a second process on the same checkout loads compiled programs
instead of compiling them again.  Tests never call it.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# A fixed path inside the checkout (listed in .gitignore): the cache only
# hits when later processes look in the same directory.
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Turn on JAX's persistent compilation cache.  Where
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
    is set here; otherwise the cache lives in :data:`CACHE_DIR`."""
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
