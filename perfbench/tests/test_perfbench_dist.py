"""The four-chip cell ``hpcg104x4.pcg.fp16`` on the CPU: a rehearsal of
``run.main`` over four forced host devices, the kind's refusal of a TPU
with fewer chips than the configuration's shards, and the cell's
per-layer readers on synthetic four-chip summaries and on the one-chip
traces of a program without their scopes, spans and gauges."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from perfbench import registry, trace

from test_perfbench_tracing import _ctx, _summary

CELL = "hpcg104x4.pcg.fp16"
HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))
NEW = ("idle_pct.pcg4", "spmv_pct.pcg4", "halo_pct.pcg4",
       "allreduce_pct.pcg4", "spmv_roofline.pcg4", "dist_build_s")
DEVICE = NEW[:5]

_REHEARSAL = r"""
import contextlib, io, json, sys
import jax
import pytest

from repro.launch import compile_cache
from repro.observe import metrics
from perfbench import run
from test_perfbench_harness import _tiny

compile_cache.use_compile_cache = lambda: "none (a rehearsal keeps no cache)"
lines = {}
for trace in ("0", "1"):
    metrics.reset()          # each run.py run is a process of its own
    with pytest.MonkeyPatch.context() as mp:
        _tiny(mp, side=8, iters=10)
        out = io.StringIO()
        with contextlib.redirect_stdout(out):
            rc = run.main(["--workload", sys.argv[1], "--seed",
                           str(2 ** 32 + 5), "--seconds", "0.3",
                           "--trace", trace])
    lines[trace] = {"rc": rc, "log": out.getvalue().splitlines()}
print(json.dumps(lines))
"""


@pytest.fixture(scope="module")
def rehearsal():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.pathsep.join([os.path.join(CHECKOUT, "src"),
                                           CHECKOUT, HERE]))
    proc = subprocess.run([sys.executable, "-c", _REHEARSAL, CELL],
                          env=env, capture_output=True, text=True,
                          timeout=600, cwd=CHECKOUT)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("traced", ["0", "1"])
def test_four_device_rehearsal_is_correct_on_four_shards(rehearsal, traced):
    run = rehearsal[traced]
    assert run["rc"] == 0
    last = json.loads(run["log"][-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == 4
    assert any("dist plan" in line and "4 shards" in line
               for line in run["log"])
    want = ({"build_s", "dist_build_s"} if traced == "1"
            else {"pcg_iter_ms", "setup_s"})
    assert want <= set(last["metrics"])
    assert all(v["value"] > 0 for v in last["metrics"].values())


def test_traced_rehearsal_reads_no_device_metric_off_a_tpu(rehearsal):
    """``trace.py`` reads the ``/device:TPU:<i>`` planes alone, so a CPU
    trace gives the device readers nothing; the host span is read."""
    last = json.loads(rehearsal["1"]["log"][-1])
    assert not set(DEVICE) & set(last["metrics"])
    assert 0 < last["metrics"]["dist_build_s"]["value"] \
        <= last["metrics"]["build_s"]["value"]


# -- the refusal -------------------------------------------------------------

class _FakeTPU:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_kind_refuses_a_tpu_with_fewer_chips_than_shards(monkeypatch):
    import jax

    bench = registry.load_benchmark()
    w = registry.workload(bench, CELL)
    cfg = registry.load_config(bench, w["config"])
    mix = registry.load_traffic(w["traffic"])
    drv = registry.load_kind(mix["kind"])(cfg, mix, 3, lambda m: None)
    monkeypatch.setattr(jax, "devices", lambda *a: [_FakeTPU()] * 3)
    with pytest.raises(RuntimeError, match="4 shards"):
        drv.setup()
    assert not hasattr(drv, "a")          # refused before any build


# -- the readers -------------------------------------------------------------

@pytest.fixture
def obs():
    from repro.observe import metrics

    prev = metrics.enable(True)
    metrics.reset()
    yield metrics
    metrics.reset()
    metrics.enable(prev)


def _four_chips():
    """Four chips, each with 100 ns of ops in a 200-ns window: 50 of SpMV
    (decode 30, epilogue 20), 10 of halo, 40 of vector work, 5 of it the
    all-reduce."""
    ops = []
    for dev in range(4):
        for name, scope, start, end in (
                ("fusion.1", "shard_map/packsell.fused_decode/gather", 0, 30),
                ("fusion.2", "shard_map/packsell.gather_epilogue/gather",
                 30, 50),
                ("fusion.3", "shard_map/packsell.halo/collective-permute",
                 50, 60),
                ("fusion.4", "shard_map/packsell.solver_vec/mul", 60, 95),
                ("all-reduce.1", "shard_map/packsell.solver_vec/"
                 "packsell.allreduce/psum", 95, 100)):
            op = trace.Op(dev, name, scope, start, end)
            op.self_ns = end - start
            ops.append(op)
    busy = {dev: [[0, 100]] for dev in range(4)}
    return trace.Summary((0, 200), ops, busy, [])


def _dist_ctx(s, n=4 * 16 ** 3, nnz=10_000):
    ctx = _ctx(s, "pcg_sets_dist", 1, n=n, nnz=nnz)
    ctx["window"]["iterations"] = 3
    return ctx


def test_readers_on_a_synthetic_four_chip_set(obs):
    s = _four_chips()
    ctx = _dist_ctx(s)
    read = {m: registry.load_metric(m) for m in NEW}
    assert read["idle_pct.pcg4"](ctx) == pytest.approx(50.0)
    assert read["spmv_pct.pcg4"](ctx) == pytest.approx(50.0)
    assert read["halo_pct.pcg4"](ctx) == pytest.approx(10.0)
    assert read["allreduce_pct.pcg4"](ctx) == pytest.approx(5.0)
    # no gauge, no span: a program older than them
    assert read["spmv_roofline.pcg4"](ctx) is None
    assert read["dist_build_s"](ctx) is None
    obs.gauge("dist.shards", 4)
    obs.gauge("dist.halo_entries", 1536)
    with obs.host_span("packsell.dist_build"):
        pass
    n = 4 * 16 ** 3
    least_s = (4 * 10_000 / 4 + 4 * (n + 1536) / 4 + 4 * n / 4) / 819e9
    # 50 ns of SpMV per chip over 3 iterations and 1 set: 4 matvecs
    assert read["spmv_roofline.pcg4"](ctx) == pytest.approx(
        100 * least_s / (50e-9 / 4))
    assert read["dist_build_s"](ctx) == pytest.approx(
        obs.raw_snapshot()["histograms"][
            ("span_s", (("span", "packsell.dist_build"),))]["sum"])


@pytest.mark.parametrize("name,kind", [("pcg16_scoped", "pcg_sets"),
                                       ("spmv16_scoped", "spmv_synced")])
def test_new_readers_read_nothing_in_a_one_chip_window(obs, tmp_path, name,
                                                       kind):
    s = _summary(name, tmp_path)
    ctx = _ctx(s, kind, 1)
    ctx["window"]["iterations"] = 3
    assert s.busy_s > 0
    for m in DEVICE:
        assert registry.load_metric(m)(ctx) is None, m


@pytest.mark.parametrize("metric", ["halo_pct.pcg4", "allreduce_pct.pcg4",
                                    "spmv_roofline.pcg4", "dist_build_s"])
def test_a_program_without_the_scopes_reads_nothing(obs, tmp_path, metric):
    """The program the ``pcg16_scoped`` trace was recorded from (decode
    and solver scopes, but no halo or all-reduce scope, no ``dist.*``
    gauge and no ``packsell.dist_build`` span) read as a distributed
    window: the readers of those find nothing, and do not raise."""
    s = _summary("pcg16_scoped", tmp_path)
    assert registry.load_metric(metric)(_dist_ctx(s)) is None
