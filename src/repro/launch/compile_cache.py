"""Where JAX's persistent compilation cache lives.

The cache is placed from outside the program: when
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing here
overrides it; otherwise the cache goes to ``.jax_cache`` at the root of the
checkout. The path is fixed: a directory named after a pid, a time or a
temporary name would never be found again by the next run.

Launchers call ``enable_compile_cache()`` in ``main`` before their first
compile; library code and tests never do, so they leave no cache behind.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
