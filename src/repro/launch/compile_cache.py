"""Where JAX keeps its persistent compilation cache.

Every entry point that runs on the chip (``chip_smoke.py``, the
``repro.launch.serve*`` launchers, ``benchmarks/run.py``) calls
:func:`enable_compile_cache` once, before its first compile, so that
processes started from one checkout share compiled kernels.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing
  here overrides it.
* unset: the cache goes to ``<checkout>/.jax_cache`` (listed in
  ``.gitignore``).  The path is fixed — never derived from a temporary
  directory, a pid or the time — because it is part of the cache key
  seen by later processes.
"""
from __future__ import annotations

import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[3]
DEFAULT_CACHE_DIR = CHECKOUT / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Every compile is cached, however short: a
    Pallas kernel compiles in well under JAX's default one-second
    threshold, and a fresh process would otherwise redo them all."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
