"""Roofline scoreboard: achieved bandwidth vs backend peak (DESIGN.md §12.4).

For every tiny-suite matrix class × codec this measures the steady-state
plan-dispatch SpMV time (interleaved across codecs per class so container
noise cancels out of the ratios, :func:`benchmarks.common.time_fns`) and
scores it against three byte models:

* **stream model** — the plan's own hot-path accounting: the fused word
  stream (or the bucketed packs) + the decode cache + x read once + y
  written once (``SpMVPlan.decode_cache_stats``).  Measured GB/s =
  stream bytes / t; this is THE figure the achieved-vs-peak fraction
  uses, matching BENCH_spmv.json's bandwidth column.
* **format model** — ``composite_memory_stats`` via
  ``plan.as_composite(mat).memory_stats()``: resident format bytes +
  vectors.  Equals the stream model when nothing is repacked; diverges
  by run-padding + checkpoint overhead on the fused path.
* **HLO cross-check** — ``launch.hlo_cost.aggregate`` over the COMPILED
  dispatch HLO: what XLA actually moves at fusion boundaries, including
  decode intermediates.  Always >= the stream model (decode materializes
  unpacked values); recorded as ``hlo_vs_model_ratio`` and gated by
  ``HLO_TOLERANCE`` — a cell is flagged when the compiled traffic is
  more than that factor off the model (fusion regression or a broken
  byte model).

The peak-bandwidth denominator comes from
:func:`repro.launch.roofline.peak_bandwidth`: the published HBM peak of
the device kind. A device with no published peak (the CPU backend) gets
no fraction: ``achieved_frac_of_peak`` is ``None`` ("not measured").

The run executes with the flight recorder enabled and embeds
``repro.observe.report()`` in the payload, so the dispatch counters /
bytes-per-nnz gauges land next to the timings they describe.

Writes ``BENCH_roofline.json`` at the repo root.  The legacy dry-run
roofline-term dump (launch-planner cells) is kept as an extra section
when an ``artifacts/dryrun*.json`` sweep artifact exists.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro import observe
from repro.core import packsell as pk
from repro.core import testmats
from repro.kernels import plan as kplan
from repro.launch import hlo_cost
from repro.launch import roofline as rl

from . import common

_JSON_PATH = os.environ.get(
    "REPRO_BENCH_ROOFLINE_JSON",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "BENCH_roofline.json"))

#: codec columns of the scoreboard: the fp16 embed (paper default) and a
#: sub-16-bit pack — the two ends of the bytes/nnz range the tiny suite
#: exercises without a per-matrix selector run.
CODECS = (("fp16", 15), ("e8m", 8))

#: flag a cell when compiled HLO bytes exceed the stream model by more
#: than this factor (the decode epilogue materializes fp32 intermediates,
#: so ~2-4x is the healthy fused-path range on CPU; >8x means XLA stopped
#: fusing the decode or the byte model broke)
HLO_TOLERANCE = float(os.environ.get("REPRO_ROOFLINE_HLO_TOL", "8.0"))


def _hlo_text(plan, mat, x) -> str:
    """Compiled optimized-HLO text of one plan dispatch (the byte
    cross-check)."""
    fn = jax.jit(plan._execute, static_argnums=(3,))
    return fn.lower(plan._exec_mat(mat), plan._device_operands(), x,
                    False).compile().as_text()


def _hlo_bytes(txt: str) -> float:
    """Bytes moved by one compiled plan dispatch, per the HLO cost model
    (static analysis of the optimized module — no execution)."""
    return float(hlo_cost.aggregate(txt)["bytes"])


def _cells(name: str, a, peak: dict | None) -> list[dict]:
    """One scoreboard row per codec for matrix class ``name`` — both
    codecs timed interleaved so the fp16-vs-packed ratio is paired."""
    a = a.tocsr()
    a.sort_indices()
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal(a.shape[1]).astype(np.float32))

    mats, plans, plans_pl = {}, {}, {}
    for codec, D in CODECS:
        key = f"{codec}{D}"
        mats[key] = pk.from_csr(a, C=32, sigma=256, D=D, codec=codec)
        plans[key] = kplan.get_plan(mats[key])
        # the pallas-fused variant of the same cell (kernel over the same
        # stream; interpret mode off-TPU). Demotes to jnp when the stream
        # is infeasible — the variant column records which one ran.
        plans_pl[key] = kplan.build_plan(mats[key], force="fused")

    ts = common.time_fns(
        {k: (lambda v, mm=mats[k], p=plans[k]: p.spmv(mm, v))
         for k in mats},
        {k: (x,) for k in mats}, rounds=15, samples=True)
    # paired jnp-fused vs pallas-fused timings, few rounds (interpret
    # mode runs the kernel body in Python off-TPU)
    pl_keys = [k for k in mats if plans_pl[k].variant == "fused"]
    ts_pl = common.time_fns(
        {k: (lambda v, mm=mats[k], p=plans_pl[k]: p.spmv(mm, v))
         for k in pl_keys},
        {k: (x,) for k in pl_keys},
        rounds=3, samples=True) if pl_keys else {}

    rows = []
    for codec, D in CODECS:
        key = f"{codec}{D}"
        mat, plan = mats[key], plans[key]
        t = float(np.median(ts[key]))
        nnz = max(int(mat.nnz), 1)

        dcs = plan.decode_cache_stats()
        vec_bytes = 4 * (mat.n + mat.m)
        stream_bytes = (dcs["fused_stream_bytes"] or 4 * plan.total_words) \
            + dcs["decode_cache_bytes"] + vec_bytes
        fmt = plan.as_composite(mat).memory_stats()
        model_bytes = fmt["composite_bytes"] + vec_bytes
        hlo_txt = _hlo_text(plan, mat, x)
        hlo = _hlo_bytes(hlo_txt)

        gbs = stream_bytes / t / 1e9
        frac = None if peak is None else gbs * 1e9 / peak["bw_bytes_per_s"]
        ratio = hlo / max(stream_bytes, 1)
        row = dict(
            klass=name, codec=codec, D=D, n=mat.n, nnz=int(mat.nnz),
            variant=plan.variant, cache_mode=plan.cache_mode,
            t_spmv_s=t,
            stream_bytes=int(stream_bytes),
            bytes_per_nnz=(stream_bytes - vec_bytes) / nnz,
            format_bytes=int(model_bytes),
            format_bytes_per_nnz=fmt["bytes_per_nnz"],
            hlo_bytes=hlo,
            hlo_vs_model_ratio=ratio,
            hlo_within_tolerance=bool(ratio <= HLO_TOLERANCE),
            measured_gbs=gbs,
            peak_gbs=None if peak is None else peak["bw_bytes_per_s"] / 1e9,
            achieved_frac_of_peak=frac,
            variant_pallas=plans_pl[key].variant,
            t_spmv_pallas_s=(float(np.median(ts_pl[key]))
                             if key in ts_pl else None),
            pallas_vs_jnp=((t / float(np.median(ts_pl[key])))
                           if key in ts_pl else None),
        )
        rows.append(row)
        common.emit("roofline_spmv", f"{name}_{key}",
                    **{k: v for k, v in row.items()
                       if k != "klass"})
    return rows


def _legacy_dryrun_cells() -> list[dict]:
    """The pre-§12 behaviour of this module: per launch-planner cell
    roofline terms read from a dry-run sweep artifact, when one exists."""
    path = os.environ.get("REPRO_DRYRUN_JSON", "")
    for c in ((path,) if path else
              ("artifacts/dryrun_optimized.json", "artifacts/dryrun_all.json")):
        if c and os.path.exists(c):
            path = c
            break
    else:
        return []
    with open(path) as f:
        cells = json.load(f)
    out = []
    for rec in cells:
        tag = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}"
        if rec["status"] != "ok":
            out.append(dict(cell=tag, status=rec["status"]))
            continue
        r = rec["roofline"]
        out.append(dict(
            cell=tag, status="ok", dominant=r["dominant"],
            t_compute_s=r["t_compute_s"], t_memory_s=r["t_memory_s"],
            t_collective_s=r["t_collective_s"],
            roofline_fraction=r["roofline_fraction"],
            useful_flops_ratio=r["useful_flops_ratio"]))
    return out


def run(scale: str | None = None) -> None:
    scale = scale or common.SCALE
    prev = observe.enable(True)          # the run records itself
    try:
        dev = jax.devices()[0]
        # the CPU has no published peak; an accelerator missing from the
        # table raises
        peak = (None if dev.platform == "cpu"
                else rl.peak_bandwidth(dev.device_kind))
        if peak is not None:
            common.emit("roofline_peak", dev.device_kind,
                        peak_gbs=peak["bw_bytes_per_s"] / 1e9,
                        source=peak["source"])
        cells = []
        for name, a in testmats.suite("tiny").items():
            cells.extend(_cells(name, a, peak))

        bad = [f"{c['klass']}/{c['codec']}{c['D']}" for c in cells
               if not c["hlo_within_tolerance"]]
        payload = dict(
            scale=scale, backend=jax.default_backend(),
            peak_bandwidth=peak,
            hlo_tolerance=HLO_TOLERANCE,
            hlo_cells_out_of_tolerance=bad,
            note=("stream model = fused word stream + decode cache + x + y "
                  "(the BENCH_spmv bandwidth convention); format model = "
                  "composite_memory_stats resident bytes + vectors; "
                  "hlo_bytes = static cost of the compiled dispatch "
                  "(includes decode intermediates, so ratio > 1 is "
                  "expected; > hlo_tolerance is flagged); "
                  "achieved_frac_of_peak divides the stream-model GB/s by "
                  "peak_bandwidth, the published HBM peak of the device "
                  "kind (null where none is published)"),
            cells=cells,
            observe_report=observe.report(),
            legacy_dryrun=_legacy_dryrun_cells(),
        )
        common.save_bench_json(_JSON_PATH, payload)
    finally:
        observe.enable(prev)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default=None)
    ns = ap.parse_args()
    run(ns.scale)
