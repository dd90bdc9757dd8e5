"""Where JAX keeps its persistent compilation cache.

Each entry point (``chip_smoke.py``, ``launch/train.py``,
``benchmarks/table1_coverage.py``) calls :func:`enable` once before its
first compile; nothing calls it at import, and tests never do.

* ``$JAX_COMPILATION_CACHE_DIR`` set: JAX reads it on start-up, and no
  other directory is set here.
* unset: the cache goes to ``<checkout>/.jax_cache``.  The path is fixed
  (never a temp name, pid or time) because a later run finds an entry
  only where an earlier one wrote it.

This is XLA's executable cache.  Terra's own artifact store
(``$TERRA_CACHE_DIR``, ``core/persist/``) is separate.
"""

from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
