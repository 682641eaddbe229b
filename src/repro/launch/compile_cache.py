"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, :mod:`repro.launch.serve`,
:mod:`repro.launch.train`) call :func:`use_compile_cache` once at start;
importing this module changes nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself and
  this module sets no other directory.
* Otherwise: ``<checkout>/.jax_cache``. The path is fixed (no temp name,
  pid or time) because it is part of the cache key: a cache that moves
  never hits.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the repository checkout this package was loaded from (``src/repro/..``)
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
