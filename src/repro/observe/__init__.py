"""PackSELL flight recorder: one observability surface for the stack.

``repro.observe`` is the metrics/tracing layer every dispatch, solve,
guard check and cache flows through (DESIGN.md §12). Recording is off by
default (``REPRO_OBS=0``); flip it with the env var or :func:`enable`.

    from repro import observe
    observe.enable()
    ...  # run solves / benchmarks
    print(json.dumps(observe.report(), indent=1))
"""
from __future__ import annotations

from .metrics import (enable, enabled, export_json, gauge, host_span,
                      host_span_handle, inc, observe, raw_snapshot,
                      record_trace, reset, snapshot, span)

__all__ = [
    "enable", "enabled", "export_json", "gauge", "host_span",
    "host_span_handle", "inc", "observe",
    "raw_snapshot", "record_trace", "record_solve", "reset", "snapshot",
    "span", "report",
    # perf-sentinel layers (DESIGN.md §13): imported as submodules to keep
    # `import repro.observe` light — `from repro.observe import export,
    # trajectory`
]


def record_solve(solver: str, info, **labels) -> None:
    """Post-hoc solver convergence trace from an ``Info`` pytree
    (``SolveInfo`` / ``AdaptiveSolveInfo``): per-outer-iteration residual
    plus tier history, emitted once the arrays are concrete — never a host
    callback inside ``lax.while_loop``. Silently skips under tracing (the
    inner ``pcg`` of a jitted fused solve sees tracers; the outer host
    wrapper records), so nesting never double-counts."""
    if not enabled():
        return
    import jax
    import numpy as np

    leaves = jax.tree_util.tree_leaves(info)
    if any(isinstance(leaf, jax.core.Tracer) for leaf in leaves):
        return
    rec: dict = {"solver": solver}
    iters = int(np.asarray(info.iters))
    rec["iters"] = iters
    rec["relres"] = float(np.asarray(info.relres))
    hist = np.asarray(info.history, dtype=np.float64)
    # history buffers are fixed-size (lax.while_loop carry): trim the
    # unwritten tail (zeros past ``iters`` entries; entry 0 is the seed)
    rec["history"] = [float(h) for h in hist[: iters + 1]]
    tiers = getattr(info, "tier_history", None)
    if tiers is not None:
        th = np.asarray(tiers)
        rec["tier_history"] = [int(t) for t in th[: iters + 1]]
    if getattr(info, "promotions", None) is not None:
        rec["promotions"] = int(np.asarray(info.promotions))
    record_trace("solver.trace", rec, solver=solver, **labels)
    inc("solver.solves", solver=solver, **labels)
    inc("solver.iters", iters, solver=solver, **labels)


def report() -> dict:
    """One-call populated snapshot: every registry series plus the live
    plan/jit cache statistics (``kernels.plan.cache_stats()`` — present
    even when recording was off, so the scoreboard always has the cache
    column)."""
    snap = snapshot()
    try:
        from repro.kernels import plan as _kplan

        snap["plan_cache"] = dict(_kplan.cache_stats())
        snap["plan_cache"]["jit_cache_cap"] = _kplan.LRUDict.default_cap()
    except Exception:  # pragma: no cover - plan layer unavailable
        snap["plan_cache"] = {}
    return snap
