"""What every traffic kind shares: the program's operator built from a
configuration, host spans, and the seeded sample of a window's answers.

A traffic mix is a data file (``traffic/<mix>.json``) that names its
``kind``; the kind is ``kinds/<kind>.py``, found by name through
``registry.load_kind``, whose ``Driver`` class has ``setup``, ``draw``,
``window``, ``hlo_texts``, ``answers``, ``free``, ``check`` and
``control_answers``. Everything that varies between cells (sizes, pools,
iteration counts) comes from the configuration and the mix files.
"""
from __future__ import annotations

import random
import time

import numpy as np

from . import registry


def span(name: str):
    """A host span the trace's idle gaps are named by."""
    import jax

    return jax.profiler.TraceAnnotation(name)


class Reservoir:
    """A uniform sample of ``size`` items from a stream, drawn from the
    seed (algorithm R)."""

    def __init__(self, size: int, seed: int):
        self.size, self.seen, self.items = size, 0, []
        self.rng = random.Random(seed)

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
            return
        j = self.rng.randrange(self.seen)
        if j < self.size:
            self.items[j] = item


def slowest(times_s: list, k: int = 3) -> list:
    """``[index, ms]`` of the ``k`` longest calls of a window, longest
    first, for the run's log."""
    order = np.argsort(times_s)[::-1][:k]
    return [[int(i), float(times_s[i]) * 1e3] for i in order]


def build_operator(cfg: dict, say) -> dict:
    """The CSR from the configuration's generator, then the program's
    pack and plan. ``build_s`` is the host clock around those two."""
    import jax

    from repro.core import packsell
    from repro.kernels import plan as kplan

    t0 = time.perf_counter()
    a = registry.load_generator(cfg["matrix"]["generator"])(cfg["matrix"])
    t_matrix = time.perf_counter() - t0
    fmt = cfg["format"]
    t0 = time.perf_counter()
    mat = packsell.from_csr(a, C=fmt["C"], sigma=fmt["sigma"], D=fmt["D"],
                            codec=fmt["codec"])
    plan = kplan.get_plan(mat)
    jax.block_until_ready((jax.tree.leaves(mat),
                           jax.tree.leaves(plan._device_operands())))
    build_s = time.perf_counter() - t0
    lens = np.diff(a.indptr)
    dcs = plan.decode_cache_stats()
    say(f"matrix {cfg['matrix']['generator']}: n={a.shape[0]} "
        f"nnz={a.nnz} max_row={int(lens.max())} "
        f"empty_rows={int((lens == 0).sum())} built in {t_matrix:.3f}s")
    say(f"pack+plan {build_s:.3f}s: variant={plan.variant} "
        f"interpret={plan.interpret} cache_mode={plan.cache_mode} "
        f"stored_words={int(mat.words_bucketed)} dummies={int(mat.n_dummy)} "
        f"layout stream_bytes={dcs['fused_stream_bytes']} "
        f"decode_cache_bytes={dcs['decode_cache_bytes']} (layout, not work)")
    return {"a": a, "mat": mat, "plan": plan, "build_s": build_s}
