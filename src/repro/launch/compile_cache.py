"""JAX's persistent compilation cache, in one place.

Every entry point (``chip_smoke.py``, ``examples/*.py``,
``benchmarks/run.py``) calls :func:`use_compile_cache` before its first
compile, so repeated runs on the same machine reuse compiled programs.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; no other
  directory is configured in code.
* unset: the cache goes to ``<checkout>/.jax_cache`` — a fixed path, since
  the path is part of the cache key (``.gitignore`` lists it).

Either way the cache key includes the programs' metadata. JAX's default
key strips it, so two programs that compile to the same instructions but
carry different ``named_scope`` paths (``observe.span``) share an entry,
and the one loaded second runs with the first one's ``op_name``s: a
profile of it names none of its own scopes.
"""
from __future__ import annotations

import os

import jax

#: the checkout root (this file is ``src/repro/launch/compile_cache.py``)
CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory, key it on
    the programs' metadata too, and return that directory
    (``JAX_COMPILATION_CACHE_DIR`` if set, else :data:`DEFAULT_DIR`)."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
