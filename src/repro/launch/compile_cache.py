"""JAX's persistent compilation cache, placed where a run can find it again.

``enable_compile_cache()`` is called by every entry point (``chip_smoke.py``,
``launch/train.py``, ``launch/serve_lda.py``, the benchmarks):

* with ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads that variable itself
  and nothing is set here;
* otherwise the cache goes to ``<checkout>/.jax_cache``. The directory is
  part of the cache key, so it is a fixed path, never a temporary or a
  pid- or time-derived one.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout this package runs from (``src/repro/launch`` → its root)
CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
