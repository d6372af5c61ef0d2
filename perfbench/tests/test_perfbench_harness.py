"""The benchmark harness on the CPU: discovery by name, the contract of
BENCHMARK.json, the work formula, the generators, and a rehearsal of each
cell at a tiny size through ``run.main`` with the chip check switched off
here: the tests stand in for ``run._devices`` and ``registry.peak``."""
from __future__ import annotations

import json
import os
import re

import jax
import numpy as np
import pytest

from perfbench import registry, run, work

BENCH = registry.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _tiny(monkeypatch, side=8, scale=9, iters=None):
    """Shrink every configuration (and optionally the PCG set) for a CPU
    rehearsal; everything else runs as on the chip."""
    load_config, load_traffic = registry.load_config, registry.load_traffic

    def config(bench, name):
        c = load_config(bench, name)
        if c["matrix"]["generator"] == "stencil27":
            c["matrix"].update(nx=side, ny=side, nz=side)
        else:
            c["matrix"].update(scale=scale)
        return c

    def traffic(name):
        t = load_traffic(name)
        if iters and "iters_per_set" in t:
            t["iters_per_set"] = iters
        return t

    monkeypatch.setattr(registry, "load_config", config)
    monkeypatch.setattr(registry, "load_traffic", traffic)
    # the CPU stands in for the chip, with the v5e's published peak
    monkeypatch.setattr(run, "_devices", lambda chips: jax.devices())
    v5e = registry.peak("TPU v5 lite")
    monkeypatch.setattr(registry, "peak", lambda kind: v5e)


# -- discovery by name -------------------------------------------------------

def test_every_piece_is_found_by_name():
    for c in BENCH["configs"]:
        cfg = registry.load_config(BENCH, c["name"])
        assert callable(registry.load_generator(cfg["matrix"]["generator"]))
    for w in BENCH["workloads"]:
        kind = registry.load_traffic(w["traffic"])["kind"]
        assert callable(registry.load_kind(kind))
        assert registry.load_limits(w["name"])
    for m in BENCH["per_layer"]:
        assert callable(registry.load_metric(m["name"]))


@pytest.mark.parametrize("load", [
    lambda: registry.load_config(BENCH, "no_such_config"),
    lambda: registry.load_traffic("no_such_mix"),
    lambda: registry.load_kind("no_such_kind"),
    lambda: registry.load_limits("no_such.cell"),
    lambda: registry.load_metric("no_such_metric"),
    lambda: registry.load_generator("no_such_generator"),
    lambda: registry.workload(BENCH, "no_such.cell"),
    lambda: registry.peak("TPU v99"),
], ids=["config", "traffic", "kind", "limits", "metric", "generator", "workload",
        "peak"])
def test_unknown_name_raises(load):
    with pytest.raises(KeyError):
        load()


# -- the contract of BENCHMARK.json -------------------------------------------

def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        cfg = json.load(open(os.path.join(registry.CHECKOUT, c["file"])))
        assert all(NAME.match(k) and k in cfg for k in c["reduced"])
        names.add(c["name"])
    cells = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in names
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        cells.add(w["name"])
    assert len(cells) == len(BENCH["workloads"])
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for cell in cells:
        reported = [m["name"] for m in
                    registry.metrics_for(BENCH["end_to_end"], cell)]
        assert "setup_s" in reported and len(reported) >= 2
        assert registry.metrics_for(BENCH["per_layer"], cell)


# -- work and generators -----------------------------------------------------

def test_spmv_min_bytes_matches_the_deployment_figures():
    # HPCG 104^3: n = 1,124,864, nnz = 310^3 = 29,791,000
    assert work.spmv_min_bytes(1124864, 1124864, 29791000) == 128162912
    # Graph500 scale 18: n = 262,144, nnz = 7,611,176 -> about 32.5 MB
    assert work.spmv_min_bytes(262144, 262144, 7611176) == 32541856


@pytest.mark.parametrize("side", [5, 8])
def test_stencil27_on_a_small_grid(side):
    a = registry.load_generator("stencil27")(
        {"nx": side, "ny": side, "nz": side, "sym_scale": True})
    n = side ** 3
    assert a.shape == (n, n) and a.nnz == (3 * side - 2) ** 3
    assert np.allclose(a.diagonal(), 1.0)
    assert abs(a - a.T).max() == 0
    assert work.spmv_min_bytes(n, n, a.nnz) == 4 * a.nnz + 8 * n


def test_kronecker_at_a_tiny_scale():
    p = {"scale": 8, "edgefactor": 16, "A": 0.57, "B": 0.19, "C": 0.19,
         "graph_seed": 3}
    g = registry.load_generator("kronecker")(p)
    assert g.shape == (256, 256)
    assert 0 < g.nnz <= 2 * 16 * 256
    assert g.diagonal().max() == 0 and abs(g - g.T).max() == 0
    assert g.data.min() > 0
    again = registry.load_generator("kronecker")(p)
    assert (g != again).nnz == 0
    lens = np.diff(g.indptr)
    assert lens.max() > 8 * max(np.median(lens), 1)   # power-law rows


# -- rehearsal of each cell --------------------------------------------------

def _run(capsys, argv):
    rc = run.main(argv)
    out = capsys.readouterr()
    return rc, out.out.strip().splitlines(), out.err


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cpu_rehearsal_prints_the_contract_line(cell, trace, monkeypatch,
                                                capsys):
    _tiny(monkeypatch, iters=10)
    rc, lines, err = _run(capsys, ["--workload", cell, "--seed",
                                   str(2 ** 31 + 17), "--seconds", "0.3",
                                   "--trace", str(trace)])
    assert rc == 0
    last = json.loads(lines[-1])
    assert list(last)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(last)[-1] == "checks"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert set(last["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        assert {"busy_s", "window_s"} <= set(last["device"])
        assert "build_s" in last["metrics"]
        assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        want = {m["name"] for m in
                registry.metrics_for(BENCH["end_to_end"], cell)}
        assert set(last["metrics"]) == want
        assert all(v["value"] > 0 for v in last["metrics"].values())
    tail = err.strip().splitlines()[-len(last["checks"]):]
    assert all(line.startswith("check ") for line in tail)


def test_same_seed_same_inputs(monkeypatch):
    _tiny(monkeypatch)
    cfg = registry.load_config(BENCH, "hpcg104_fp16")
    mix = registry.load_traffic("spmv")
    kind = registry.load_kind(mix["kind"])
    d1 = kind(cfg, mix, 5_000_000_000, lambda m: None)
    d2 = kind(cfg, mix, 5_000_000_000, lambda m: None)
    d1.a = d2.a = registry.load_generator("stencil27")(cfg["matrix"])
    d1.draw(d1.seed)
    d2.draw(d2.seed)
    assert np.array_equal(d1.x_host, d2.x_host)


def test_refuses_a_device_that_is_not_a_tpu(capsys):
    rc, lines, err = _run(capsys, ["--workload", CELLS[0], "--seed", "1",
                                   "--seconds", "1", "--trace", "0"])
    assert rc != 0
    assert not any(line.startswith("{") for line in lines)
    assert "not 'tpu'" in err
