"""Span-level device-time attribution (DESIGN.md §13.1).

The PR-7 recorder counts *dispatches*; this module answers *where inside
a dispatch the time goes*.  :func:`profile_dispatch` runs a callable
under ``jax.profiler.trace``, parses the captured Chrome-trace events,
and buckets per-op device time under the PR-7 span names
(``packsell.fused_decode``, ``gather_epilogue``, ...).

How attribution works (two event sources, one join):

* The profiler emits one event per executed HLO thunk, tagged with the
  post-optimization instruction name (``args.hlo_op``) and module
  (``args.hlo_module``) — but NOT the ``named_scope`` path.
* The *compiled HLO text* of the dispatched executable carries each
  instruction's ``metadata={op_name="jit(f)/.../packsell.fused_decode/
  ..."}`` — the scope path ``observe.span`` planted.  (For fusions the
  metadata is the fusion root's, which inherits the root's scope.)
* :func:`hlo_span_map` parses that text into ``(module, op) -> span``;
  trace events then join against it.  Ops whose scope path names no
  known span are aggregated into a top-k ``unattributed`` list — an op
  showing up there means a hot region nobody wrapped in a span yet.

Host-side ``TraceAnnotation`` intervals whose name IS a span name (the
eager-solver ``packsell.solver_while`` wrapper) are credited as host
time for that span.  The whole measured region is bracketed by a marker
annotation, so ``wall_s`` is the real per-call dispatch wall time,
including host overhead the device events cannot see.

When the profiler plugin is unavailable (no trace produced, or the
trace API raises) on a non-TPU backend, :func:`profile_dispatch`
degrades to a pure wall-clock measurement with
``profiler_unavailable=True`` — CPU CI keeps running, and consumers
(``bench_roofline --profile``) surface the marker instead of fabricating a
breakdown. On a TPU a failed trace raises: a wall clock is not a device
time.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import json
import os
import re
import shutil
import tempfile
import time

from . import metrics as _obs

__all__ = ["SPAN_NAMES", "SpanProfile", "hlo_span_map", "profile_dispatch"]

#: the fixed span vocabulary of DESIGN.md §12.2 — attribution targets
#: (``bucket_decode`` covers the non-fused bucketed/cursor dispatch body,
#: added when span profiling surfaced it as 100%-unattributed)
SPAN_NAMES = (
    "packsell.plan_build",
    "packsell.fused_decode",
    "packsell.fused_kernel",
    "packsell.bucket_decode",
    "packsell.gather_epilogue",
    "packsell.halo_prestage",
    "packsell.guard_checksum",
    "packsell.solver_while",
)

#: marker annotation bracketing each measured call
_MARKER = "packsell.profile_dispatch"

#: instruction definition with op_name metadata, post-optimization HLO
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%([A-Za-z0-9_.\-]+)\s*=\s*.*"
    r"metadata=\{[^}]*op_name=\"([^\"]+)\"")
_MODULE_RE = re.compile(r"^HloModule\s+([^,\s]+)", re.MULTILINE)


@dataclasses.dataclass
class SpanProfile:
    """Per-span time attribution for one dispatched callable.

    ``spans`` maps span name -> ``{"device_s", "host_s", "ops",
    "events"}`` (seconds are per-call averages across ``repeats``).
    ``coverage_of_wall`` = attributed span device time / clean wall; on
    tiny CPU dispatches this is structurally small because the wall is
    host-dispatch-bound, so the breakdown also carries an explicit
    ``host_overhead_s`` bucket and ``accounted_frac_of_wall`` = (device
    + host overhead) / wall — the ">= 0.8" acceptance figure: either
    the spans explain the wall, or the profile says out loud that the
    dispatch is host-overhead-bound (and by how much)."""

    mode: str                       # "trace" | "wallclock"
    backend: str
    repeats: int
    wall_s: float                   # per-call dispatch wall, no profiler
    traced_wall_s: float = 0.0      # per-call wall under the trace (the
    #                                 marker interval; includes per-thunk
    #                                 TraceMe instrumentation cost)
    device_total_s: float = 0.0     # per-call, all hlo-op events
    host_overhead_s: float = 0.0    # wall - device time: pjit python
    #                                 dispatch, argument parsing, buffer
    #                                 await — the part no HLO op covers
    spans: dict = dataclasses.field(default_factory=dict)
    unattributed: list = dataclasses.field(default_factory=list)
    attributed_frac: float = 0.0    # of device_total_s
    coverage_of_wall: float = 0.0   # span device time / wall
    accounted_frac_of_wall: float = 0.0   # (span + unattributed device
    #                                 + host overhead) / wall — how much
    #                                 of the wall the breakdown explains
    profiler_unavailable: bool = False
    note: str = ""

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def hlo_span_map(hlo_text: str, spans=SPAN_NAMES) -> dict:
    """Parse post-optimization HLO text into ``(module, op) -> span``.
    An op maps to the FIRST span name appearing as a path component of
    its ``op_name`` metadata (named_scope components are exact path
    segments; transform wrappers like ``jit(...)`` never collide)."""
    m = _MODULE_RE.search(hlo_text)
    module = m.group(1) if m else ""
    spanset = set(spans)
    out = {}
    for line in hlo_text.splitlines():
        im = _INSTR_RE.match(line)
        if not im:
            continue
        op, op_name = im.groups()
        for comp in op_name.split("/"):
            if comp in spanset:
                out[(module, op)] = comp
                break
    return out


def _find_trace_json(trace_dir: str) -> str | None:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    return hits[-1] if hits else None


def _parse_events(path: str) -> list[dict]:
    with gzip.open(path, "rt") as f:
        payload = json.load(f)
    return [e for e in payload.get("traceEvents", [])
            if e.get("ph") == "X" and "dur" in e]


def _wallclock(fn, args, repeats: int, backend: str,
               note: str) -> SpanProfile:
    t0 = time.perf_counter()
    for _ in range(repeats):
        _block(fn(*args))
    wall = (time.perf_counter() - t0) / max(repeats, 1)
    return SpanProfile(mode="wallclock", backend=backend, repeats=repeats,
                       wall_s=wall, profiler_unavailable=True, note=note)


def _block(out):
    import jax

    jax.block_until_ready(out)
    return out


def profile_dispatch(fn, *args, spans=SPAN_NAMES, hlo_texts=(),
                     repeats: int = 10, warmup: int = 2,
                     top_k: int = 8) -> SpanProfile:
    """Profile ``repeats`` calls of ``fn(*args)`` and attribute device
    time to named spans.

    ``hlo_texts``: compiled-HLO module texts of the executables ``fn``
    dispatches (builds the op->span join).  When ``fn`` itself is a
    jit-wrapped callable its lowering is harvested automatically; for a
    host wrapper around a cached dispatch (``plan.spmv``) pass the text
    explicitly — ``bench_roofline`` reuses the lowering it already does
    for the HLO byte cross-check."""
    import jax

    backend = jax.default_backend()
    # the recorder must be ON from the first (compiling) call: span() is
    # a bare yield when disabled, and a function traced that way bakes an
    # HLO with no scope metadata — nothing to attribute.  Callers whose
    # executables were compiled recorder-off should rebuild/clear their
    # jit caches before profiling.
    prev = _obs.enable(True)
    try:
        for _ in range(max(warmup, 1)):        # compile outside the trace
            _block(fn(*args))
        t0 = time.perf_counter()               # clean wall: what a bench
        for _ in range(repeats):               # measures, no per-thunk
            _block(fn(*args))                  # TraceMe instrumentation
        wall_clean = (time.perf_counter() - t0) / max(repeats, 1)

        texts = list(hlo_texts)
        if not texts and hasattr(fn, "lower"):
            try:
                texts.append(fn.lower(*args).compile().as_text())
            except Exception:
                pass
    finally:
        _obs.enable(prev)
    span_map = {}
    for txt in texts:
        span_map.update(hlo_span_map(txt, spans))
    by_op = {}                     # op-name fallback when module unmatched
    for (_, op), s in span_map.items():
        by_op[op] = s

    td = tempfile.mkdtemp(prefix="repro_profile_")
    prev = _obs.enable(True)       # host-side span annotations must fire
    try:
        try:
            with jax.profiler.trace(td):
                t0 = time.perf_counter()
                for _ in range(repeats):
                    with jax.profiler.TraceAnnotation(_MARKER):
                        _block(fn(*args))
                t_wall = time.perf_counter() - t0
        except Exception as e:     # profiler plugin unavailable/busy
            if backend == "tpu":
                raise RuntimeError(
                    f"profile_dispatch: TPU trace failed: {e!r}") from e
            return _wallclock(fn, args, repeats, backend,
                              f"trace failed: {e!r}")
        finally:
            _obs.enable(prev)
        tj = _find_trace_json(td)
        if tj is None:
            if backend == "tpu":
                raise RuntimeError(
                    "profile_dispatch: TPU trace produced no trace.json.gz")
            return _wallclock(fn, args, repeats, backend,
                              "no trace.json.gz produced")
        events = _parse_events(tj)
    finally:
        _obs.enable(prev)
        shutil.rmtree(td, ignore_errors=True)

    spanset = set(spans)
    acc = {s: {"device_s": 0.0, "host_s": 0.0, "ops": 0, "events": 0}
           for s in spans}
    marker_us = 0.0
    device_us = 0.0
    unattr: dict = {}
    for e in events:
        name = e["name"]
        dur = float(e["dur"])      # microseconds
        eargs = e.get("args") or {}
        if name == _MARKER:
            marker_us += dur
            continue
        if "hlo_op" in eargs:
            device_us += dur
            key = (eargs.get("hlo_module", ""), eargs["hlo_op"])
            span = span_map.get(key) or by_op.get(eargs["hlo_op"])
            if span is not None:
                acc[span]["device_s"] += dur * 1e-6
                acc[span]["events"] += 1
            else:
                unattr[name] = unattr.get(name, 0.0) + dur
            continue
        if name in spanset:        # host TraceAnnotation from observe.span
            acc[name]["host_s"] += dur * 1e-6
            acc[name]["events"] += 1

    reps = max(repeats, 1)
    for (_, op), s in span_map.items():
        acc[s]["ops"] += 1
    for s in acc.values():
        s["device_s"] /= reps
        s["host_s"] /= reps
    traced = (marker_us * 1e-6 / reps) if marker_us else t_wall / reps
    dev_total = device_us * 1e-6 / reps
    span_dev = sum(s["device_s"] for s in acc.values())
    top = sorted(unattr.items(), key=lambda kv: -kv[1])[:top_k]
    note = ""
    # host overhead: the wall the device ops do not explain.  On a CPU
    # backend with many small thunks the per-thunk TraceMe cost can
    # inflate summed device durations past the clean wall — clamp and
    # say so rather than report a negative host share.
    host_over = wall_clean - dev_total
    if host_over < 0:
        host_over = 0.0
        note = ("summed device events exceed the untraced wall "
                "(per-thunk instrumentation inflation); host overhead "
                "clamped to 0")
    accounted = min((dev_total + host_over) / wall_clean, 1.0) \
        if wall_clean else 0.0
    return SpanProfile(
        mode="trace", backend=backend, repeats=repeats, wall_s=wall_clean,
        traced_wall_s=traced,
        device_total_s=dev_total,
        host_overhead_s=host_over,
        spans={k: v for k, v in acc.items()
               if v["events"] or v["ops"]},
        unattributed=[{"op": k, "device_s": v * 1e-6 / reps}
                      for k, v in top],
        attributed_frac=(span_dev / dev_total) if dev_total else 0.0,
        coverage_of_wall=(span_dev / wall_clean) if wall_clean else 0.0,
        accounted_frac_of_wall=accounted,
        note=note,
    )
