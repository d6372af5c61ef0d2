"""Process-local metrics registry and trace spans (DESIGN.md §12).

The flight recorder behind every PackSELL dispatch: counters, gauges and
histograms keyed by ``(name, labels)``, plus ``span()`` context managers
that name hot regions in XLA profiles. Two invariants shape the design:

* **Zero-cost when disabled.** ``REPRO_OBS=0`` (the tier-1 default) makes
  every recording call a single predicate check and ``span()`` a bare
  ``yield`` — no dict lookups, no allocation, no lock.
* **Jit-compatible.** Recording happens only at host-side dispatch entry
  points; code inside a ``jax.jit``-traced body runs once at trace time,
  so counters there would silently freeze. ``span()`` *is* legal inside
  traced code — ``jax.named_scope`` only attaches metadata to the ops it
  encloses and ``jax.profiler.TraceAnnotation`` marks host trace-time —
  neither can change numerics, which is what the REPRO_OBS=1 bit-for-bit
  parity tests pin down.

``host_span()`` times host work that no device op covers (packing, plan
construction, dispatch): a ``TraceAnnotation`` on the profiler's host
plane, on the device ops' clock, plus its host seconds in the histogram
``span_s{span=<name>}``.

Series naming follows ``subsystem.event`` with labels for dimensions, e.g.
``spmv.dispatch{cache_mode=checkpoint,codec=fp16,variant=jnp}``. Span and
scope names come from :data:`SPAN_NAMES`; the full naming map lives in
DESIGN.md §12.
"""
from __future__ import annotations

import contextlib
import json
import os
import random
import threading
import time

__all__ = [
    "enabled", "enable", "inc", "gauge", "observe", "record_trace",
    "series_key", "inc_many", "counter_bump", "snapshot", "raw_snapshot",
    "reset", "export_json", "span", "host_span", "host_span_handle",
    "SPAN_NAMES",
]

#: The program's span and scope names (DESIGN.md §12.2). Device scopes,
#: planted by :func:`span` as ``named_scope`` metadata on the ops they
#: enclose (``x_gather`` nests inside the decode scopes; the solver's
#: vector work and its σ-permutes never nest inside an SpMV scope; the
#: distributed solve's ``allreduce`` nests inside ``solver_vec`` and its
#: ``halo`` exchange inside none):
_SCOPE_NAMES = (
    "packsell.fused_decode",
    "packsell.fused_kernel",
    "packsell.bucket_decode",
    "packsell.x_gather",
    "packsell.gather_epilogue",
    "packsell.halo",
    "packsell.allreduce",
    "packsell.guard_checksum",
    "packsell.solver_while",
    "packsell.solver_vec",
    "packsell.stored_permute",
)
#: host spans, timed by :func:`host_span` (a dotted suffix names a stage
#: of its parent):
_HOST_SPAN_NAMES = (
    "packsell.pack",
    "packsell.pack.encode",
    "packsell.pack.words",
    "packsell.pack.slices",
    "packsell.pack.to_device",
    "packsell.plan_build",
    "packsell.plan_build.stream",
    "packsell.plan_build.cache",
    "packsell.plan_build.inverse",
    "packsell.plan_build.window",
    "packsell.plan_build.to_device",
    "packsell.dispatch",
    "packsell.dist_build",
    "packsell.dist_build.partition",
    "packsell.dist_build.members",
    "packsell.dist_build.stack",
    "packsell.dist_build.to_device",
)
SPAN_NAMES = _SCOPE_NAMES + _HOST_SPAN_NAMES


def _env_on(val: str | None) -> bool:
    return (val or "0").strip().lower() not in ("", "0", "false", "off", "no")


_ENABLED = _env_on(os.environ.get("REPRO_OBS"))

_LOCK = threading.Lock()
# series key: (name, (("k","v"), ...)) with labels sorted by key
_COUNTERS: dict = {}
_GAUGES: dict = {}
_HISTS: dict = {}          # key -> {"count", "sum", "min", "max", "last"}
_TRACES: dict = {}         # key -> list of records (bounded)
_TRACE_CAP = 256           # per-series record cap (drop-oldest)
_RES_CAP = 256             # per-histogram quantile reservoir size
_QUANTILES = (("p50", 0.50), ("p95", 0.95), ("p99", 0.99))
# Algorithm-R replacement draws: a dedicated seeded stream so reservoir
# contents are reproducible per process and never perturb user RNG state
_RES_RNG = random.Random(0xC0FFEE)


def enabled() -> bool:
    """True when the registry records (``REPRO_OBS`` truthy or enable())."""
    return _ENABLED


def enable(on: bool = True) -> bool:
    """Flip recording on/off at runtime (benchmarks/tests; the env var
    only sets the process default). Returns the previous state."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(on)
    return prev


def _key(name: str, labels: dict):
    if not labels:
        return (name, ())
    return (name, tuple(sorted((k, str(v)) for k, v in labels.items())))


def inc(name: str, value: float = 1, **labels) -> None:
    """Add ``value`` to counter ``name{labels}`` (no-op when disabled)."""
    if not _ENABLED:
        return
    k = _key(name, labels)
    with _LOCK:
        _COUNTERS[k] = _COUNTERS.get(k, 0) + value


def series_key(name: str, **labels):
    """Precompute a series handle for :func:`inc_many` — hot dispatch
    paths pay the label sort/stringification once at plan setup instead
    of on every call (the <3% overhead budget of DESIGN.md §12.5)."""
    return _key(name, labels)


def inc_many(pairs) -> None:
    """Bump several precomputed ``(series_key, value)`` counters — the
    steady-state dispatch record.  Deliberately lock-free: each get/set
    is GIL-atomic, so the only cross-thread hazard is a lost increment
    when two threads interleave on the SAME series — acceptable for a
    flight recorder, and it keeps the hot dispatch path inside the §12.5
    overhead budget (the lock acquisition costs as much as both bumps)."""
    if not _ENABLED:
        return
    for k, v in pairs:
        _COUNTERS[k] = _COUNTERS.get(k, 0) + v


def counter_bump(pairs):
    """Compile ``(series_key, value)`` pairs into a zero-arg closure —
    the cheapest possible steady-state record (everything resolvable is
    bound at build time; the common two-counter case is unrolled).  The
    closure re-checks ``_ENABLED`` so a cached bump goes quiet when the
    recorder is turned off.  Same lock-free tradeoff as
    :func:`inc_many`."""
    pairs = tuple(pairs)
    C = _COUNTERS          # reset() clears in place, never rebinds
    if len(pairs) == 2:
        (k1, v1), (k2, v2) = pairs

        def bump(C=C, get=C.get):
            if _ENABLED:
                C[k1] = get(k1, 0) + v1
                C[k2] = get(k2, 0) + v2
        return bump

    def bump(C=C, get=C.get):
        if _ENABLED:
            for k, v in pairs:
                C[k] = get(k, 0) + v
    return bump


def gauge(name: str, value: float, **labels) -> None:
    """Set gauge ``name{labels}`` to the latest ``value``."""
    if not _ENABLED:
        return
    k = _key(name, labels)
    with _LOCK:
        _GAUGES[k] = value


def observe(name: str, value: float, **labels) -> None:
    """Record ``value`` into histogram ``name{labels}``: count/sum/min/
    max/last aggregates plus a bounded reservoir (Algorithm R, cap
    ``_RES_CAP``) that :func:`snapshot` turns into p50/p95/p99 — serving
    latency SLOs need percentiles, not means.  Still strictly a no-op
    when the recorder is disabled."""
    if not _ENABLED:
        return
    k = _key(name, labels)
    with _LOCK:
        _hist_add(k, float(value))


def _hist_add(k, v: float) -> None:
    """Add ``v`` to histogram series ``k``. Callers hold ``_LOCK``, except
    the host spans, which take the lock-free tradeoff of :func:`inc_many`
    (a rare lost observation between threads on one series)."""
    h = _HISTS.get(k)
    if h is None:
        _HISTS[k] = {"count": 1, "sum": v, "min": v, "max": v,
                     "last": v, "res": [v]}
        return
    n = h["count"] = h["count"] + 1
    h["sum"] += v
    if v < h["min"]:
        h["min"] = v
    if v > h["max"]:
        h["max"] = v
    h["last"] = v
    res = h["res"]
    if n <= _RES_CAP:
        res.append(v)
    else:
        # uniform reservoir: each of the n values seen so far keeps an
        # equal _RES_CAP/n chance of being resident (random() is a third
        # of randrange()'s cost on the per-call host-span path)
        j = int(_RES_RNG.random() * n)
        if j < _RES_CAP:
            res[j] = v


def _quantiles(res: list) -> dict:
    """Nearest-rank percentiles from a reservoir sample (exact when the
    series has fewer than ``_RES_CAP`` observations)."""
    s = sorted(res)
    n = len(s)
    return {tag: s[min(int(q * n), n - 1)] for tag, q in _QUANTILES}


def record_trace(name: str, record: dict, **labels) -> None:
    """Append a structured record (e.g. one solve's convergence history)
    to trace series ``name{labels}``; oldest records drop past the cap."""
    if not _ENABLED:
        return
    k = _key(name, labels)
    with _LOCK:
        lst = _TRACES.setdefault(k, [])
        lst.append(record)
        if len(lst) > _TRACE_CAP:
            del lst[: len(lst) - _TRACE_CAP]


def _fmt_key(k) -> str:
    name, labels = k
    if not labels:
        return name
    return name + "{" + ",".join(f"{a}={b}" for a, b in labels) + "}"


def _hist_view(h: dict) -> dict:
    """Exported histogram record: aggregates + reservoir percentiles (the
    raw reservoir stays private to the registry)."""
    out = {k: v for k, v in h.items() if k != "res"}
    out.update(_quantiles(h["res"]))
    return out


def snapshot() -> dict:
    """Point-in-time copy of every series, keyed ``name{k=v,...}``."""
    with _LOCK:
        return {
            "enabled": _ENABLED,
            "counters": {_fmt_key(k): v for k, v in sorted(_COUNTERS.items())},
            "gauges": {_fmt_key(k): v for k, v in sorted(_GAUGES.items())},
            "histograms": {_fmt_key(k): _hist_view(v)
                           for k, v in sorted(_HISTS.items())},
            "traces": {_fmt_key(k): [dict(r) for r in v]
                       for k, v in sorted(_TRACES.items())},
        }


def raw_snapshot() -> dict:
    """Structured twin of :func:`snapshot` for machine consumers (the
    exporters): series keyed by the raw ``(name, ((label, value), ...))``
    tuples instead of formatted strings, so no string parsing is ever
    needed to recover labels.  Traces are excluded — they are logs, not
    metrics."""
    with _LOCK:
        return {
            "counters": dict(_COUNTERS),
            "gauges": dict(_GAUGES),
            "histograms": {k: _hist_view(v) for k, v in _HISTS.items()},
        }


def reset() -> None:
    """Clear every series (the enabled flag is left as-is)."""
    with _LOCK:
        _COUNTERS.clear()
        _GAUGES.clear()
        _HISTS.clear()
        _TRACES.clear()


def export_json(path: str) -> dict:
    """Write :func:`snapshot` to ``path``; returns the snapshot."""
    snap = snapshot()
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(snap, f, indent=1, default=float)
    return snap


@contextlib.contextmanager
def span(name: str):
    """Name a hot region in XLA profiles: ``jax.named_scope`` tags the ops
    traced inside (visible in HLO metadata / device profiles) and
    ``TraceAnnotation`` marks the host-side interval. Single bare yield
    when disabled. Safe inside jit-traced code — metadata only."""
    if not _ENABLED:
        yield
        return
    import jax

    with jax.named_scope(name), jax.profiler.TraceAnnotation(name):
        yield


_NULL_SPAN = contextlib.nullcontext()


class _HostSpan:
    """One interval of a host span: a ``TraceAnnotation`` while a profiler
    collects (the C++ ``TraceMe`` check is all it costs otherwise), and
    its host seconds into histogram ``key``."""

    __slots__ = ("_name", "_key", "_ann_cls", "_ann", "_t0")

    def __init__(self, name: str, key, ann_cls):
        self._name, self._key, self._ann_cls = name, key, ann_cls
        self._ann = None

    def __enter__(self):
        if self._ann_cls.is_enabled():
            self._ann = self._ann_cls(self._name)
            self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        if self._ann is not None:
            self._ann.__exit__(*exc)
        _hist_add(self._key, dt)
        return False


def host_span(name: str, **labels):
    """Time host work as span ``name``: a ``jax.profiler.TraceAnnotation``
    on the profiler's host plane (same clock as the device ops) and the
    host seconds into histogram ``span_s{span=name, **labels}``. A shared
    null context when disabled: one predicate check. Host work only —
    inside a jit trace it would time tracing, not execution."""
    if not _ENABLED:
        return _NULL_SPAN
    from jax.profiler import TraceAnnotation

    return _HostSpan(name, _key("span_s", dict(labels, span=name)),
                     TraceAnnotation)


def host_span_handle(name: str, **labels):
    """Prebuilt :func:`host_span` for a per-call path: returns a zero-arg
    factory of the span with the series key and annotation class bound
    once, so a call pays no label sort, import or lock (the §12.5
    budget). The factory re-checks the enabled flag on every call."""
    from jax.profiler import TraceAnnotation

    key = _key("span_s", dict(labels, span=name))

    def handle():
        if not _ENABLED:
            return _NULL_SPAN
        return _HostSpan(name, key, TraceAnnotation)
    return handle
