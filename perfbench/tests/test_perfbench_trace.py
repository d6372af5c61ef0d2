"""The trace reduction (``perfbench/trace.py``) on small synthetic cases and
on traces recorded on one TPU v5e by ``perfbench/record_trace.py`` (16^3
HPCG grid: synced fp16 SpMV calls, and one 3-iteration PCG set), each
kept, gzipped, beside the compiled HLO its window ran."""
from __future__ import annotations

import gzip
import os

import pytest

from perfbench import registry, trace, work

DATA = os.path.join(os.path.dirname(__file__), "data")


def _summary(name, tmp_path):
    with gzip.open(os.path.join(DATA, f"{name}.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    pb = tmp_path / f"{name}.xplane.pb"
    with gzip.open(os.path.join(DATA, f"{name}.xplane.pb.gz"), "rb") as f:
        pb.write_bytes(f.read())
    return trace.summarize(str(pb), [hlo])


# -- synthetic --------------------------------------------------------------

def test_union_merges_overlapping_and_touching_intervals():
    assert trace._union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 10)]) == \
        [[0, 4], [5, 7], [9, 10]]


def test_nested_ops_count_their_self_time_once():
    loop = trace.Op(0, "while.1", "jit(f)/packsell.solver_while", 0, 100)
    a = trace.Op(0, "fusion.1", "jit(f)/packsell.fused_decode/gather", 10, 50)
    b = trace.Op(0, "fusion.2", "jit(f)/add", 60, 90)
    c = trace.Op(0, "fusion.3", "jit(f)/mul", 120, 130)
    trace._self_times([c, b, a, loop])
    assert (loop.self_ns, a.self_ns, b.self_ns, c.self_ns) == (30, 40, 30, 10)
    s = trace.Summary((0, 200), [loop, a, b, c],
                      {0: trace._union([(0, 100), (10, 50), (60, 90),
                                        (120, 130)])},
                      [("bench.wait", 100, 120), ("bench.dispatch", 130, 200)])
    assert s.busy_s == pytest.approx(110e-9)
    assert s.op_time_s() == pytest.approx(s.busy_s)
    assert s.op_time_s(("packsell.fused_decode",)) == pytest.approx(40e-9)
    assert sorted(s.gaps()) == [("bench.dispatch", pytest.approx(70e-9)),
                                ("bench.wait", pytest.approx(20e-9))]


# -- recorded on the chip ---------------------------------------------------

def _ctx(s, kind, calls, n=16 ** 3, nnz=(3 * 16 - 2) ** 3):
    return {"kind": kind, "trace": s, "window": {"calls": calls},
            "shape": (n, n), "nnz": nnz, "build_s": 1.0,
            "peak": registry.peak("TPU v5 lite")}


def test_chip_spmv_trace_reduces_to_its_metrics(tmp_path):
    s = _summary("spmv16", tmp_path)
    calls = sum(1 for h in s.host if h[0] == "bench.dispatch")
    assert calls >= 3
    assert 0 < s.busy_s < s.window_s
    # busy is the union of the op intervals; nothing nests in an SpMV, so
    # the ops' self times add up to it
    assert s.op_time_s() == pytest.approx(s.busy_s, rel=1e-9)
    gaps = sum(g for _, g in s.gaps())
    assert gaps + s.busy_s == pytest.approx(s.window_s, rel=1e-9)
    decode = s.op_time_s(("packsell.fused_decode",))
    epilogue = s.op_time_s(("packsell.gather_epilogue",))
    unscoped = sum(op.self_ns for op in s.ops if not op.scope) * 1e-9
    assert decode > epilogue > 0
    assert decode + epilogue + unscoped == pytest.approx(s.op_time_s())
    ctx = _ctx(s, "spmv_synced", calls)
    idle = registry.load_metric("idle_pct.spmv")(ctx)
    assert idle == pytest.approx(100 * (1 - s.busy_s / s.window_s))
    assert 0 < registry.load_metric("decode_pct.spmv")(ctx) < 100
    roof = registry.load_metric("spmv_roofline")(ctx)
    least = work.spmv_min_bytes(16 ** 3, 16 ** 3, 46 ** 3) / 819e9
    assert roof == pytest.approx(100 * least / (s.busy_s / calls))
    assert 0 < roof < 100
    # the PCG readers find nothing to read in an SpMV window
    assert registry.load_metric("spmv_pct.pcg")(ctx) is None
    assert registry.load_metric("idle_pct.pcg")(ctx) is None
    out = s.breakdown()
    assert out["device_ops"][0][0].startswith("packsell.fused_decode:")
    assert {g[0] for g in out["idle_gaps"]} <= {"bench.dispatch",
                                                "bench.wait", "host.other"}


def test_chip_pcg_trace_reduces_to_its_metrics(tmp_path):
    s = _summary("pcg16", tmp_path)
    assert 0 < s.busy_s < s.window_s
    loops = [op for op in s.ops if op.name.startswith("while")]
    assert loops and all(0 <= op.self_ns < op.end_ns - op.start_ns
                         for op in loops)
    assert s.op_time_s() == pytest.approx(s.busy_s, rel=1e-6)
    ctx = _ctx(s, "pcg_sets", 1)
    share = registry.load_metric("spmv_pct.pcg")(ctx)
    assert 0 < share < 100
    assert 0 < registry.load_metric("idle_pct.pcg")(ctx) < 100
    assert registry.load_metric("spmv_roofline")(ctx) is None
