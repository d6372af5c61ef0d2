"""Reduce a JAX profiler trace of one window to the numbers the per-layer
metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes,
read with ``jax.profiler.ProfileData``. Three things are taken from it:

* the window: the host span ``bench.window`` that each traffic kind plants;
* the device's operations: the events of the ``XLA Ops`` line of each
  ``/device:TPU:<i>`` plane, clipped to the window. Busy time is the union
  of their intervals, averaged over the chips; idle is the rest of the
  window;
* each operation's scope: the program's ``jax.named_scope`` path
  (``packsell.fused_decode`` and the like). A TPU op event is named by its
  HLO instruction (``%fusion.1 = f32[...] fusion(...)``) and carries no
  scope, so the instruction name is joined to the ``metadata={op_name=..}``
  of the compiled HLO of the programs the window ran (``hlo_scopes``; the
  join of ``repro.observe.profile.hlo_span_map``).

Idle gaps are named by the benchmark's host span (``bench.dispatch``,
``bench.wait``) that covers the middle of the gap, or ``host.other``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_OP_LINE = "XLA Ops"
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%([A-Za-z0-9_.\-]+)\s*=.*?'
                    r'metadata=\{[^}]*op_name="([^"]*)"')
_EVENT_INSTR = re.compile(r"^%?([A-Za-z0-9_.\-]+)")
HOST_SPANS = ("bench.dispatch", "bench.wait")


def find(tdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {tdir}")
    return paths[-1]


def hlo_scopes(texts) -> dict:
    """``{instruction name: op_name}`` from compiled HLO texts."""
    out = {}
    for text in texts:
        for line in text.splitlines():
            m = _INSTR.match(line)
            if m:
                out[m.group(1)] = m.group(2)
    return out


def _instr(event_name: str) -> str:
    m = _EVENT_INSTR.match(event_name)
    return m.group(1) if m else event_name


@dataclasses.dataclass
class Op:
    device: int
    name: str
    scope: str
    start_ns: float
    end_ns: float
    self_ns: float = 0.0      # duration less that of the ops nested in it


def _self_times(ops: list) -> None:
    """Control flow nests on the op line (a ``while`` event spans its
    body's ops): give each op its duration less its direct children's."""
    stack = []
    for op in sorted(ops, key=lambda o: (o.start_ns, -o.end_ns)):
        op.self_ns = op.end_ns - op.start_ns
        while stack and stack[-1].end_ns <= op.start_ns:
            stack.pop()
        if stack:
            stack[-1].self_ns -= min(op.end_ns, stack[-1].end_ns) \
                - op.start_ns
        stack.append(op)


def _union(intervals: list) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Summary:
    window_ns: tuple
    ops: list                 # Op, clipped to the window
    busy: dict                # device -> merged [start, end] intervals
    host: list                # (name, start_ns, end_ns) of bench spans

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        if not self.busy:
            return 0.0
        tot = sum(e - s for iv in self.busy.values() for s, e in iv)
        return tot / len(self.busy) * 1e-9

    def op_time_s(self, scopes=None) -> float:
        """Summed self time of the ops whose scope path holds one of
        ``scopes`` (all ops when None), averaged over the chips."""
        if not self.busy:
            return 0.0
        tot = sum(op.self_ns for op in self.ops
                  if scopes is None or any(s in op.scope for s in scopes))
        return tot / len(self.busy) * 1e-9

    def gaps(self) -> list:
        """``(host span, seconds)`` of each idle gap in the window, on the
        first chip."""
        if not self.busy:
            return []
        iv = self.busy[min(self.busy)]
        w0, w1 = self.window_ns
        edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
        out = []
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                mid = (s + e) / 2
                name = next((h for h, hs, he in self.host if hs <= mid < he),
                            "host.other")
                out.append((name, (e - s) * 1e-9))
        return out

    def breakdown(self, top: int = 10) -> dict:
        per = {}
        for op in self.ops:
            leaf = op.scope.split("/")
            key = "/".join(x for x in leaf if x.startswith("packsell."))
            key = f"{key}:{op.name}" if key else op.name
            per[key] = per.get(key, 0.0) + op.self_ns * 1e-9
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps(), key=lambda g: -g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def summarize(path: str, hlo_texts=()) -> Summary:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    return summarize_data(pd, hlo_scopes(hlo_texts))


def summarize_data(pd, scopes=None) -> Summary:
    scopes = scopes or {}
    host, window = [], None
    dev_events = {}
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == _OP_LINE:
                    dev_events.setdefault(dev, []).extend(
                        (_instr(ev.name), ev.start_ns, ev.end_ns)
                        for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == "bench.window":
                        window = (ev.start_ns, ev.end_ns)
                    elif ev.name in HOST_SPANS:
                        host.append((ev.name, ev.start_ns, ev.end_ns))
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    w0, w1 = window
    ops, busy = [], {}
    for dev, evs in sorted(dev_events.items()):
        mine = [Op(dev, name, scopes.get(name, ""), max(s, w0), min(e, w1))
                for name, s, e in evs if min(e, w1) > max(s, w0)]
        _self_times(mine)
        ops.extend(mine)
        busy[dev] = _union([(op.start_ns, op.end_ns) for op in mine])
    host.sort(key=lambda h: h[1])
    return Summary(window, ops, busy, host)


def idle_pct(ctx: dict, kind: str):
    """Device idle share of a traced window of traffic ``kind``:
    100 (1 - busy / window), busy being the union of the device's
    operation intervals in the window. None for another kind, or where
    the trace holds no device operation."""
    t = ctx["trace"]
    if ctx["kind"] != kind or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
