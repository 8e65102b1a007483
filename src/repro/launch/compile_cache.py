"""JAX's persistent compilation cache, placed from outside or at a fixed
path in the checkout.

A cold start on an accelerator compiles every program the run touches —
the genome-scale build alone takes minutes.  Entry points call
:func:`enable_compile_cache` before their first compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing is
  set here;
* otherwise the cache lives at ``<checkout>/.jax_cache`` (gitignored).
  The path is fixed — never a temporary name, a pid or the time —
  because a directory that moves never hits.

Tests leave the cache off (``tests/conftest.py``).
"""
from __future__ import annotations

import os

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path
    import jax
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
