"""Keep each harness test from changing the state of the test process:
``run.py`` turns on the program's recorder, configures JAX's persistent
cache and freezes the garbage collector's objects, which the repository's
other tests must not see."""
from __future__ import annotations

import gc

import pytest

_KEYS = ("jax_enable_x64", "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True)
def _restore_process_state(monkeypatch):
    import jax

    from repro.launch import compile_cache
    from repro.observe import metrics as obs

    monkeypatch.setattr(compile_cache, "use_compile_cache",
                        lambda: "none (tests keep no persistent cache)")
    was_on = obs.enabled()
    prev = {k: jax.config.values[k] for k in _KEYS}
    yield
    gc.unfreeze()
    obs.enable(was_on)
    for k, v in prev.items():
        jax.config.update(k, v)
