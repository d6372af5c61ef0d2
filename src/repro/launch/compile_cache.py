"""JAX's persistent compilation cache, in one place.

Every entry point (``chip_smoke.py``, ``examples/*.py``,
``benchmarks/run.py``) calls :func:`use_compile_cache` before its first
compile, so repeated runs on the same machine reuse compiled programs.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing else is
  configured in code.
* unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path, since
  the path is part of the cache key (``.gitignore`` lists it).
"""
from __future__ import annotations

import os

import jax

#: the checkout root (this file is ``src/repro/launch/compile_cache.py``)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory (``JAX_COMPILATION_CACHE_DIR`` if set, else
    :data:`DEFAULT_DIR`)."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
