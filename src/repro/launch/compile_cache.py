"""JAX's persistent compilation cache, switched on by the entry points.

The launchers, ``benchmarks/run.py`` and ``chip_smoke.py`` call
``enable_compile_cache()`` first thing; importing ``repro`` never does, so
the CPU test suite writes no cache.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and this
sets no other directory.  Otherwise the cache lives at ``.jax_cache/`` in
the checkout: a fixed path, because the path is part of what a later run
must find again.
"""

from __future__ import annotations

import os
from pathlib import Path

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it writes to."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
