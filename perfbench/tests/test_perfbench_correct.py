"""What decides ``correct``, on the CPU at a small size: each control of a
cell (the reference, or the program's own path, with one stated precision
one step lower) fails the cell's limits, and a run whose timed path is
broken underneath reads ``correct`` false for each fault the cell can
have."""
from __future__ import annotations

import json

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import registry, run

from test_perfbench_harness import BENCH, CELLS, _tiny

KIND = {w["name"]: registry.load_traffic(w["traffic"])["kind"]
        for w in BENCH["workloads"]}
SPMV = [c for c in CELLS if KIND[c] == "spmv_synced"]
PCG = [c for c in CELLS if KIND[c] == "pcg_sets"]


@pytest.mark.parametrize("seed", [11, 12, 13])
@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(cell, seed, monkeypatch):
    _tiny(monkeypatch, side=12, scale=10, iters=20)
    w = registry.workload(BENCH, cell)
    cfg = registry.load_config(BENCH, w["config"])
    mix = registry.load_traffic(w["traffic"])
    run._configure_jax()
    d = registry.load_kind(mix["kind"])(cfg, mix, seed, lambda m: None)
    d.setup()
    limits = registry.load_limits(cell)
    tried = []
    for key, lower in cfg["control"].items():
        answers = d.control_answers(key, lower)
        if answers is None:
            continue
        checks = d.check(answers, cfg["precision"]["values"])
        assert any(c[k] > limits[k] for c in checks for k in c), \
            (key, lower, checks)
        tried.append(key)
    assert "values" in tried and len(tried) == 2


def _last(capsys, cell):
    rc = run.main(["--workload", cell, "--seed", "424242", "--seconds",
                   "0.3", "--trace", "0"])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def _break_spmv(monkeypatch, fault):
    from repro.kernels import plan as kplan

    spmv = kplan.SpMVPlan.spmv

    def broken(self, mat, x, **kw):
        y = spmv(self, mat, x, **kw)
        if fault == "state_unchanged":
            return x
        if fault == "half_left_out":
            return y.at[y.shape[0] // 2:].set(0.0)
        return y.at[y.shape[0] // 3].add(1.0)     # an answer altered

    monkeypatch.setattr(kplan.SpMVPlan, "spmv", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered"])
@pytest.mark.parametrize("cell", SPMV)
def test_broken_spmv_reads_not_correct(cell, fault, monkeypatch, capsys):
    _tiny(monkeypatch)
    assert _last(capsys, cell)["correct"] is True
    _break_spmv(monkeypatch, fault)
    last = _last(capsys, cell)
    assert last["correct"] is False and last["failed"] > 0


def _break_pcg(monkeypatch, fault):
    from repro.solvers import cg

    solve = cg.jacobi_pcg_stored

    def broken(mat, plan, diag, b, **kw):
        if fault == "half_left_out":
            b = b.at[b.shape[0] // 2:].set(0.0)
        if fault == "float32_vectors":
            kw["dtype"] = jnp.float32
        x, info = solve(mat, plan, diag, b, **kw)
        if fault == "state_unchanged":
            return jnp.zeros_like(x), info
        if fault == "answer_altered":
            return x.at[x.shape[0] // 3].add(1.0), info
        return x, info

    monkeypatch.setattr(cg, "jacobi_pcg_stored", broken)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_left_out",
                                   "answer_altered", "float32_vectors"])
@pytest.mark.parametrize("cell", PCG)
def test_broken_pcg_reads_not_correct(cell, fault, monkeypatch, capsys):
    _tiny(monkeypatch, side=12, iters=20)
    assert _last(capsys, cell)["correct"] is True
    _break_pcg(monkeypatch, fault)
    last = _last(capsys, cell)
    assert last["correct"] is False and last["failed"] > 0


def test_reference_rounding_matches_numpy_types():
    from perfbench import reference

    v = np.array([1.0, -1.0 / 26.0, 0.3, 1e-3])
    assert np.array_equal(reference.round_values(v, "float16"),
                          v.astype(np.float32).astype(np.float16))
    # e8m7 is bfloat16's layout: the same round-to-nearest-even
    assert np.array_equal(reference.round_values(v, "e8m7"),
                          reference.round_values(v, "bfloat16"))
    e14 = reference.round_values(v, "e8m14")
    assert np.all(np.abs(e14 - v) <= np.abs(v) * 2.0 ** -15)
