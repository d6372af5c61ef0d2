"""Perf sentinel (DESIGN.md §13): exporters and the noise-aware
benchmark regression gate.

The load-bearing guarantees:

* **Prometheus round-trip** — ``prometheus_text`` → ``parse_prometheus_
  text`` reproduces the registry exactly: dotted names (via # HELP),
  label values with quotes/backslashes/newlines, histogram summaries
  with reservoir quantiles;
* **JSONL sink under fire** — concurrent flushers + a registry reset
  mid-stream produce only whole records, monotone sequence numbers, and
  a rebase marker instead of negative deltas;
* **trajectory schema contract** — every committed BENCH_*.json ingests
  (they all carry the schema-versioned meta header); a pre-schema file
  is rejected with an error that says how to fix it;
* **gate statistics** — the two-threshold design: single-class noise
  within severe_tol passes, correlated multi-class drift fails, and a
  synthetic 2x slowdown on ONE class fails (the severe path).
"""
from __future__ import annotations

import glob
import json
import os
import threading

import numpy as np
import pytest

from repro import observe
from repro.observe import export, metrics, trajectory

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def obs_on():
    prev = observe.enable(True)
    observe.reset()
    yield
    observe.reset()
    observe.enable(prev)


# ---------------------------------------------------------------------------
# reservoir quantiles (metrics satellite)
# ---------------------------------------------------------------------------


def test_histogram_quantiles_in_snapshot(obs_on):
    for i in range(1, 1001):
        metrics.observe("q.test", float(i))
    snap = observe.snapshot()
    h = snap["histograms"]["q.test"]
    assert h["count"] == 1000
    # cap-256 reservoir over a uniform ramp: quantiles are approximate
    assert 350 <= h["p50"] <= 650
    assert h["p95"] >= 800
    assert h["p99"] >= 850
    assert h["p50"] <= h["p95"] <= h["p99"] <= h["max"] == 1000


def test_small_histogram_quantiles_exact(obs_on):
    for v in (1.0, 2.0, 3.0, 4.0):
        metrics.observe("q.small", v)
    h = observe.snapshot()["histograms"]["q.small"]
    # below the reservoir cap the sample IS the population: nearest-rank
    assert h["p50"] == 3.0 and h["p99"] == 4.0


def test_observe_disabled_records_nothing():
    prev = observe.enable(False)
    try:
        metrics.observe("q.off", 1.0)
        assert metrics.raw_snapshot()["histograms"] == {}
    finally:
        observe.enable(prev)


# ---------------------------------------------------------------------------
# Prometheus exposition round-trip
# ---------------------------------------------------------------------------


def _populate():
    metrics.inc("spmv.dispatch", 3, variant="jnp", codec="fp16")
    metrics.inc("spmv.dispatch", 2, variant="band", codec="e8m")
    metrics.inc("serving.tick", 7)
    metrics.gauge("spmv.bytes_per_nnz", 7.51, codec="fp16")
    metrics.gauge("weird.gauge", -2.5, note='quo"te', path="a\\b", nl="x\ny")
    for v in (0.1, 0.2, 0.4, 0.8):
        metrics.observe("solver.time_s", v, solver="pcg")


def test_prometheus_round_trip_exact(obs_on):
    _populate()
    snap = metrics.raw_snapshot()
    text = export.prometheus_text()
    back = export.parse_prometheus_text(text)
    assert back["counters"] == snap["counters"]
    assert back["gauges"] == snap["gauges"]
    assert set(back["histograms"]) == set(snap["histograms"])
    for k, h in snap["histograms"].items():
        assert back["histograms"][k] == {
            f: h[f] for f in ("p50", "p95", "p99",
                              "count", "sum", "min", "max", "last")}


def test_prometheus_text_shape(obs_on):
    _populate()
    text = export.prometheus_text()
    assert "# HELP spmv_dispatch spmv.dispatch" in text
    assert "# TYPE spmv_dispatch counter" in text
    assert 'quantile="0.5"' in text
    assert "solver_time_s_count" in text
    # escaped label values stay on one sample line
    [line] = [l for l in text.splitlines() if l.startswith("weird_gauge")]
    assert '\\n' in line and '\\"' in line


def test_prometheus_rejects_malformed():
    with pytest.raises(ValueError, match="malformed"):
        export.parse_prometheus_text("# TYPE x counter\nx{ 1\n")
    with pytest.raises(ValueError, match="no # TYPE"):
        export.parse_prometheus_text("nosuch 1\n")


# ---------------------------------------------------------------------------
# JSONL sink
# ---------------------------------------------------------------------------


def test_jsonl_sink_delta_semantics(obs_on, tmp_path):
    p = tmp_path / "m.jsonl"
    sink = export.JsonlSink(str(p), meta={"run": "t1"})
    metrics.inc("c.a", 5)
    sink.flush()
    metrics.inc("c.a", 2)
    metrics.gauge("g.b", 1.5)
    sink.flush()
    recs = export.JsonlSink.read(str(p))
    assert recs[0]["kind"] == "meta" and recs[0]["run"] == "t1"
    assert recs[1]["counters"] == {"c.a": 5}
    assert recs[2]["counters"] == {"c.a": 2}
    assert recs[2]["gauges"]["g.b"] == 1.5


def test_jsonl_sink_rebase_after_reset(obs_on, tmp_path):
    p = tmp_path / "m.jsonl"
    sink = export.JsonlSink(str(p))
    metrics.inc("c.a", 10)
    sink.flush()
    observe.reset()
    metrics.inc("c.a", 3)          # absolute 3 < last-flushed 10
    sink.flush()
    recs = export.JsonlSink.read(str(p))
    assert recs[-1]["rebased"] is True
    assert recs[-1]["counters"] == {"c.a": 3}


def test_jsonl_sink_concurrent_flush_and_reset(obs_on, tmp_path):
    p = tmp_path / "m.jsonl"
    sink = export.JsonlSink(str(p))
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            sink.flush()

    threads = [threading.Thread(target=hammer) for _ in range(4)]
    for t in threads:
        t.start()
    for i in range(300):
        metrics.inc("c.hot", 1, lane=str(i % 3))
        metrics.observe("h.hot", float(i % 7))
        if i == 150:
            observe.reset()
    stop.set()
    for t in threads:
        t.join()
    sink.flush()
    recs = export.JsonlSink.read(str(p))    # every line parsed = whole
    assert recs[0]["kind"] == "meta"
    deltas = [r for r in recs[1:] if r["kind"] == "delta"]
    assert [r["seq"] for r in deltas] == list(range(len(deltas)))
    for r in deltas:
        assert all(v >= 0 for v in r["counters"].values())


def test_exporter_thread_clean_shutdown(obs_on, tmp_path):
    p = tmp_path / "exp.jsonl"
    exp = export.start_exporter(interval_s=0.05, path=str(p))
    try:
        metrics.inc("c.exp", 4)
        import time
        time.sleep(0.2)
    finally:
        exp.stop()
    assert not exp.alive
    recs = export.JsonlSink.read(str(p))
    total = sum(r.get("counters", {}).get("c.exp", 0)
                for r in recs if r["kind"] == "delta")
    assert total == 4                       # final flush lost nothing
    n = len(recs)
    exp.stop()                              # idempotent
    assert len(export.JsonlSink.read(str(p))) == n


# ---------------------------------------------------------------------------
# trajectory schema contract
# ---------------------------------------------------------------------------


def test_ingest_accepts_every_committed_bench_file():
    files = sorted(glob.glob(os.path.join(REPO, "BENCH_*.json")))
    assert files, "no committed BENCH files?"
    for f in files:
        recs = trajectory.ingest(f)
        assert recs, f"{f} produced no trajectory records"
        for r in recs:
            assert r["schema_version"] >= 1
            assert {"bench", "klass", "metric", "value"} <= set(r)


def test_ingest_rejects_pre_schema_file(tmp_path):
    p = tmp_path / "BENCH_old.json"
    p.write_text(json.dumps({"scale": "small", "rows": [{"t": 1.0}]}))
    with pytest.raises(trajectory.SchemaError,
                       match="pre-schema-version"):
        trajectory.ingest(str(p))
    p2 = tmp_path / "BENCH_v0.json"
    p2.write_text(json.dumps({"meta": {"schema_version": 0}, "rows": []}))
    with pytest.raises(trajectory.SchemaError, match="schema_version"):
        trajectory.ingest(str(p2))


def test_ingest_spmv_yields_gated_metric():
    recs = trajectory.ingest(os.path.join(REPO, "BENCH_spmv.json"))
    keys = {(r["bench"], r["metric"]) for r in recs}
    assert ("spmv", "dispatch_cached_s") in keys
    assert ("spmv", "fused_speedup_vs_pr1") in keys


# ---------------------------------------------------------------------------
# gate statistics
# ---------------------------------------------------------------------------


def _recs(**times):
    """Synthetic gated records: klass -> dispatch_cached_s."""
    return [{"bench": "spmv", "klass": k, "codec": "", "scale": "tiny",
             "metric": "dispatch_cached_s", "value": v,
             "git_sha": "t", "backend": "cpu"}
            for k, v in times.items()]


def _baseline():
    runs = [_recs(a=1.00, b=2.00, c=4.00),
            _recs(a=1.05, b=1.95, c=4.10),
            _recs(a=0.95, b=2.05, c=3.90)]
    return trajectory.build_baseline(runs)


def test_gate_passes_clean():
    res = trajectory.gate(_recs(a=1.02, b=1.98, c=4.05), _baseline())
    assert res["ok"] and not res["regressed"]
    assert len(res["checked"]) == 3


def test_gate_single_class_noise_passes():
    # one class +40%: above rel_tol but below severe_tol, only 1 cell
    res = trajectory.gate(_recs(a=1.40, b=2.00, c=4.00), _baseline())
    assert res["ok"]
    assert len(res["regressed"]) == 1 and not res["severe"]


def test_gate_fails_on_synthetic_2x_single_class():
    # the acceptance self-test: 2x slowdown in ONE bench class must fail
    res = trajectory.gate(_recs(a=2.00, b=2.00, c=4.00), _baseline())
    assert not res["ok"]
    assert len(res["severe"]) == 1
    assert res["severe"][0]["klass"] == "a"


def test_gate_fails_on_correlated_drift():
    # +40% on two classes: each alone tolerable (see the single-class
    # test above), together a real slowdown -> min_classes=2 trips
    res = trajectory.gate(_recs(a=1.40, b=2.80, c=4.00), _baseline())
    assert not res["ok"] and len(res["regressed_classes"]) == 2


def test_gate_iqr_widens_threshold():
    # a key whose baseline reps are wildly dispersed gets a wider lane
    runs = [_recs(a=1.0), _recs(a=2.0), _recs(a=1.5)]
    base = trajectory.build_baseline(runs)
    res = trajectory.gate(_recs(a=2.2), base)     # +47% vs median 1.5
    assert res["ok"], res        # 3x IQR/median = 2.0 > observed drift


def test_gate_direction_inversion():
    runs = [[{"bench": "roofline", "klass": "k", "codec": "fp16",
              "metric": "achieved_frac_of_peak", "value": 0.30,
              "scale": "tiny", "git_sha": "t", "backend": "cpu"}]] * 3
    base = trajectory.build_baseline(runs)
    cur = [dict(runs[0][0], value=0.10)]          # higher-is-better fell 3x
    res = trajectory.gate(cur, base)
    assert not res["ok"] and res["severe"]


def test_gate_scale_mismatch_skips():
    base = _baseline()
    cur = _recs(a=5.0)
    for r in cur:
        r["scale"] = "small"
    res = trajectory.gate(cur, base)
    assert res["ok"]
    assert res["skipped"] and "scale mismatch" in res["skipped"][0]["reason"]


def test_baseline_save_load_round_trip(tmp_path):
    p = tmp_path / "base.json"
    trajectory.save_baseline(_baseline(), str(p))
    assert trajectory.load_baseline(str(p))["entries"]
    bad = {"meta": {"schema_version": 99}, "entries": {}}
    p2 = tmp_path / "bad.json"
    p2.write_text(json.dumps(bad))
    with pytest.raises(trajectory.SchemaError, match="perf-baseline"):
        trajectory.load_baseline(str(p2))


# ---------------------------------------------------------------------------
# wiring: save_bench_json + serving endpoint
# ---------------------------------------------------------------------------


def test_save_bench_json_embeds_report_and_archive(obs_on, tmp_path,
                                                  monkeypatch):
    from benchmarks import common
    monkeypatch.setenv("REPRO_OBS_ARCHIVE_DIR", str(tmp_path / "obs"))
    metrics.inc("c.bench", 2)
    out = tmp_path / "BENCH_x.json"
    common.save_bench_json(str(out), {"rows": [{"klass": "k", "t_s": 1.0}]})
    d = json.loads(out.read_text())
    assert d["meta"]["schema_version"] >= 1
    assert d["observe_report"]["counters"]["c.bench"] == 2
    arch = export.JsonlSink.read(str(tmp_path / "obs" / "BENCH_x.jsonl"))
    assert arch[0]["kind"] == "meta"
    assert arch[0]["bench_file"] == "BENCH_x.json"
    assert arch[1]["counters"]["c.bench"] == 2
    # and the file it wrote ingests cleanly
    assert trajectory.ingest(str(out))


def test_metrics_endpoint_text_serves_registry(obs_on):
    # endpoint formatting only — engine construction is covered by
    # test_observe; the endpoint is a thin prometheus_text wrapper
    metrics.inc("serving.tick", 3)
    from repro.serving.engine import DecodeEngine
    text = DecodeEngine.metrics_endpoint_text(
        type("E", (), {})())           # no engine state touched
    assert "serving_tick 3" in text
