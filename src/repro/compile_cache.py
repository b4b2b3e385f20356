"""JAX's persistent compilation cache, placed the same way by every entry
point (the test suite, the tools, the benchmarks and ``chip_smoke.py``).

The cache is ``JAX_COMPILATION_CACHE_DIR`` where that is set, and otherwise
the fixed ``<repo>/.jax_cache``.  The directory never depends on a
temporary name, a pid or the time: a cache that moves between runs never
hits.  ``REPRO_NO_JAX_CACHE=1`` leaves the cache off.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compilation_cache() -> Optional[str]:
    """Point JAX's persistent cache at its directory; call before the
    first compile.  Returns the directory, or None when the cache is
    left off."""
    if os.environ.get("REPRO_NO_JAX_CACHE"):
        return None
    cache_dir = (os.environ.get("JAX_COMPILATION_CACHE_DIR")
                 or str(REPO_CACHE_DIR))
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)
    return cache_dir
