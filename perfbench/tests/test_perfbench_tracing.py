"""The per-layer metrics that read the program's own scopes, host spans and
plan gauges: ``gather_pct.spmv``, ``word_ns.spmv``, ``vector_pct.pcg``
(device scopes), ``pack_s``, ``plan_s``, ``dispatch_us.spmv`` (host
spans) — on traces recorded on one TPU v5e by ``perfbench/record_trace.py``
from a program with those scopes (``*16_scoped``) and from one without
them (``spmv16``/``pcg16``), where they read nothing."""
from __future__ import annotations

import gzip
import os
import re

import pytest

from perfbench import recorder, registry, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
NEW = ("gather_pct.spmv", "word_ns.spmv", "vector_pct.pcg", "pack_s",
       "plan_s", "dispatch_us.spmv")


@pytest.fixture
def obs():
    """The program's recorder, on and empty; its state restored after."""
    from repro.observe import metrics

    prev = metrics.enable(True)
    metrics.reset()
    yield metrics
    metrics.reset()
    metrics.enable(prev)


def _load(name, tmp_path):
    with gzip.open(os.path.join(DATA, f"{name}.hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    pb = tmp_path / f"{name}.xplane.pb"
    with gzip.open(os.path.join(DATA, f"{name}.xplane.pb.gz"), "rb") as f:
        pb.write_bytes(f.read())
    return str(pb), hlo


def _summary(name, tmp_path):
    pb, hlo = _load(name, tmp_path)
    return trace.summarize(pb, [hlo])


def _ctx(s, kind, calls, n=16 ** 3, nnz=(3 * 16 - 2) ** 3):
    return {"kind": kind, "trace": s, "window": {"calls": calls},
            "shape": (n, n), "nnz": nnz, "build_s": 1.0,
            "peak": registry.peak("TPU v5 lite")}


def _read(name, ctx):
    return registry.load_metric(name)(ctx)


# -- a program without the scopes and spans -----------------------------------

@pytest.mark.parametrize("name,kind", [("spmv16", "spmv_synced"),
                                       ("pcg16", "pcg_sets")])
def test_new_readers_read_nothing_from_an_older_program(obs, tmp_path,
                                                        name, kind):
    s = _summary(name, tmp_path)
    ctx = _ctx(s, kind, sum(1 for h in s.host if h[0] == "bench.dispatch"))
    assert s.busy_s > 0
    for m in NEW:
        assert _read(m, ctx) is None, m


# -- the program's registry ---------------------------------------------------

def test_registry_readers_read_the_program_spans_and_gauges(obs):
    import time

    with obs.host_span("packsell.pack"):
        time.sleep(0.003)
    with obs.host_span("packsell.pack.words"):
        pass
    with obs.host_span("packsell.plan_build"):
        time.sleep(0.001)
    handle = obs.host_span_handle("packsell.dispatch", kind="spmv")
    for _ in range(5):
        with handle():
            pass
    obs.gauge("plan.decode_words", 1000, variant="jnp", codec="fp16",
              cache_mode="checkpoint")
    s = trace.Summary((0, 10_000), [], {0: [[0, 8_000]]}, [])
    ctx = _ctx(s, "spmv_synced", 2)
    assert _read("pack_s", ctx) == pytest.approx(
        recorder.span("packsell.pack")["sum"])
    assert _read("pack_s", ctx) >= 0.003
    assert _read("plan_s", ctx) >= 0.001
    disp = recorder.span("packsell.dispatch", kind="spmv")
    assert disp["count"] == 5
    assert _read("dispatch_us.spmv", ctx) == pytest.approx(disp["p50"] * 1e6)
    # 8 us busy over 2 calls, 1000 words a call
    assert _read("word_ns.spmv", ctx) == pytest.approx(4.0)
    # the dispatch and word readers are SpMV metrics
    pcg = _ctx(s, "pcg_sets", 1)
    assert _read("dispatch_us.spmv", pcg) is None
    assert _read("word_ns.spmv", pcg) is None
    # two plans that disagree leave the per-word time unread
    obs.gauge("plan.decode_words", 2000, variant="jnp", codec="e8m",
              cache_mode="full")
    assert recorder.gauge("plan.decode_words") is None
    assert _read("word_ns.spmv", ctx) is None


def test_traced_cpu_rehearsal_reports_the_host_span_metrics(obs, monkeypatch,
                                                            capsys):
    """On the CPU the trace holds no TPU plane, so the device readers read
    nothing; the host-span readers read the run's own spans."""
    import json

    import jax

    from perfbench import run

    monkeypatch.setattr(run, "_devices", lambda chips: jax.devices())
    v5e = registry.peak("TPU v5 lite")
    monkeypatch.setattr(registry, "peak", lambda kind: v5e)
    real = registry.load_config

    def small(bench, name):
        cfg = real(bench, name)
        cfg["matrix"].update(nx=8, ny=8, nz=8)
        return cfg

    monkeypatch.setattr(registry, "load_config", small)
    assert run.main(["--workload", "hpcg104.spmv.fp16", "--seed",
                     str(2 ** 31 + 29), "--seconds", "0.2",
                     "--trace", "1"]) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    got = last["metrics"]
    assert {"pack_s", "plan_s", "dispatch_us.spmv"} <= set(got)
    assert 0 < got["pack_s"]["value"] < got["build_s"]["value"]
    assert 0 < got["plan_s"]["value"] < got["build_s"]["value"]
    assert got["dispatch_us.spmv"]["unit"] == "us"
    assert 0 < got["dispatch_us.spmv"]["value"] < 1e6


# -- a program with the scopes and spans, recorded on the chip ----------------

def _scopes(line):
    m = re.search(r'op_name="([^"]*)"', line)
    return None if m is None else [
        c for c in m.group(1).split("/") if c.startswith("packsell.")]


def _hlo(name):
    with gzip.open(os.path.join(DATA, f"{name}.hlo.txt.gz"), "rt") as f:
        return f.read()


def _decode_words_of_the_recorded_plan(obs):
    """The ``plan.decode_words`` gauge of the plan the recording built
    (``record_trace.py``: the fp16 configuration at a 16^3 grid); the plan
    build is host code, so the CPU builds the same plan."""
    from perfbench import drivers

    cfg = registry.load_config(registry.load_benchmark(), "hpcg104_fp16")
    cfg["matrix"].update(nx=16, ny=16, nz=16)
    drivers.build_operator(cfg, lambda msg: None)
    return recorder.gauge("plan.decode_words")


def test_recorded_spmv_program_gathers_x_under_its_own_scope():
    gathers = [line for line in _hlo("spmv16_scoped").splitlines()
               if " gather(" in line
               and "packsell.fused_decode" in (_scopes(line) or [])]
    assert gathers and all("packsell.x_gather" in _scopes(line)
                           for line in gathers)


def test_scoped_spmv_trace_reads_the_kernel_metrics(obs, tmp_path):
    s = _summary("spmv16_scoped", tmp_path)
    calls = sum(1 for h in s.host if h[0] == "bench.dispatch")
    words = _decode_words_of_the_recorded_plan(obs)
    # the recording's own log: "layout stream_bytes=524288"
    assert calls >= 3 and words == 524288 // 4
    ctx = _ctx(s, "spmv_synced", calls)
    gather = _read("gather_pct.spmv", ctx)
    # x_gather nests inside the decode scope: a part of decode_pct.spmv
    assert 0 < gather < _read("decode_pct.spmv", ctx) < 100
    assert gather == pytest.approx(
        100 * s.op_time_s(("packsell.x_gather",)) / s.op_time_s())
    assert _read("word_ns.spmv", ctx) == pytest.approx(
        s.busy_s / calls / words * 1e9)
    # a PCG reader finds nothing in an SpMV window
    assert _read("vector_pct.pcg", ctx) is None


def test_scoped_pcg_trace_covers_the_set_with_two_shares(obs, tmp_path):
    s = _summary("pcg16_scoped", tmp_path)
    ctx = _ctx(s, "pcg_sets", 1)
    spmv, vec = _read("spmv_pct.pcg", ctx), _read("vector_pct.pcg", ctx)
    assert 0 < vec < spmv < 100
    # a 3-iteration set at 16^3: the rest is the loop op's own time and
    # argument handling, which no scope of the program can name
    assert spmv + vec >= 98.0
    for op in s.ops:
        path = [c for c in op.scope.split("/") if c.startswith("packsell.")]
        if path == ["packsell.solver_while"]:
            assert op.name.startswith("while"), op
    assert _read("gather_pct.spmv", ctx) is None
    # in the compiled loop body no instruction is left with the loop's
    # scope alone
    hlo = _hlo("pcg16_scoped")
    loop = [line for line in hlo.splitlines() if " while(" in line
            and 'op_name="jit(solve)/packsell.solver_while/while"' in line]
    assert len(loop) == 1
    body = re.search(r"body=%?([\w.\-]+)", loop[0]).group(1)
    lines = hlo.split(f"%{body} ", 1)[1].split("\n}", 1)[0].splitlines()
    paths = [p for p in map(_scopes, lines[1:]) if p is not None]
    assert len(paths) > 100
    assert not any(p == ["packsell.solver_while"] for p in paths)
    assert any("packsell.stored_permute" in p for p in paths)


def test_dispatch_spans_cover_each_launch_on_the_host_plane(tmp_path):
    """One ``packsell.dispatch`` host event per call, on a host plane,
    inside the benchmark's ``bench.dispatch`` and around the runtime's
    launch of that call (``DoEnqueueProgram``). Matched by ``run_id`` to
    the device's module and the host's completion, the launches bound the
    device clock's offset from the host's: the merged profile puts the
    device 1.2-1.6 ms early here, so its raw times must not be compared
    with host spans."""
    from jax.profiler import ProfileData

    pb, _ = _load("spmv16_scoped", tmp_path)
    with open(pb, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    disp, calls, enq, mods, done = [], [], [], {}, {}
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                span = (ev.start_ns, ev.end_ns)
                if ev.name == "packsell.dispatch":
                    assert plane.name.startswith("/host:")
                    disp.append(span)
                elif ev.name == "bench.dispatch":
                    calls.append(span)
                elif ev.name == "DoEnqueueProgram":
                    enq.append(span)
                elif line.name == "XLA Modules":
                    mods[int(dict(ev.stats)["run_id"])] = span
                elif ev.name == "CompleteCallbacks":
                    done[int(dict(ev.stats)["run_id"])] = ev.start_ns
    disp, calls, enq = sorted(disp), sorted(calls), sorted(enq)
    assert len(disp) == len(calls) == len(enq) == len(mods) >= 3
    for (d0, d1), (c0, c1), (e0, e1) in zip(disp, calls, enq):
        assert c0 <= d0 < e0 < e1 <= d1 <= c1
    # device start after its launch, device end before its completion
    runs = sorted(mods)
    latest = min(mods[r][0] - e1 for r, (_, e1) in zip(runs, enq))
    earliest = max(mods[r][1] - done[r] for r in runs if r in done)
    assert earliest <= latest < -1_000_000
