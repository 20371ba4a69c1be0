"""JAX's persistent compilation cache for the repo's entry-point scripts.

A compiled heartbeat at deployment scale takes tens of seconds to
compile, so every script that drives the engine (``chip_smoke.py``, the
examples, the benchmarks) calls ``enable_compile_cache()`` first.  The
library never does this on import.
"""
from __future__ import annotations

import os
import pathlib

#: The fixed in-checkout cache directory (listed in ``.gitignore``).  It
#: never depends on a temp name, a pid or the time, so the next run of
#: the same checkout finds what this one compiled.
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing else is configured; otherwise the cache goes to
    ``CACHE_DIR``."""
    import jax

    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
