"""Readings that set a cell's correctness limits (``limits/<cell>.json``).

    python3 perfbench/calibrate.py --workload hpcg104.spmv.fp16 \\
        --seeds 101-112 --control-seeds 201-203 --seconds 6 \\
        --out chiprun_out/calib_hpcg104.spmv.fp16.json

One process, one set-up. For each program seed it draws that seed's
inputs, runs a short window of the cell's own timed path and checks it as
``run.py`` does, keeping the worst reading of each compared number. For
each control seed and each entry of the configuration's ``control`` (a
precision key and the step below what ``precision`` states) that the
cell's traffic kind uses, it puts that control in the program's place
(``Driver.control_answers``: the reference at the lower precision, or the
program's own lower-precision path where it has one) and reads the same
numbers. The lower reading of a number is the largest over the program
seeds, the upper the smallest over every control and control seed; each
control's own smallest is kept as well, since each control has to fail
some number. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def _seeds(text: str) -> list:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def worst(checks: list) -> dict:
    return {k: max(c[k] for c in checks) for k in checks[0]}


def controls(drv, cfg: dict, prec: str) -> dict:
    """``{key: worst readings}`` of each control the driver's kind has,
    for the pool drawn last."""
    out = {}
    for key, lower in cfg["control"].items():
        answers = drv.control_answers(key, lower)
        if answers is not None:
            out[f"{key}={lower}"] = worst(drv.check(answers, prec))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last")
    ap.add_argument("--control-seeds", required=True, help="first-last")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()

    from perfbench import registry, run

    bench = registry.load_benchmark()
    cell = registry.workload(bench, args.workload)
    cfg = registry.load_config(bench, cell["config"])
    mix = registry.load_traffic(cell["traffic"])
    run._configure_jax()
    run._devices(int(cell["chips"]))
    prec = cfg["precision"]["values"]
    seeds = _seeds(args.seeds)
    drv = registry.load_kind(mix["kind"])(cfg, mix, seeds[0], run.say)
    drv.setup()
    program, control = {}, {}
    for seed in seeds:
        drv.draw(seed)
        res = drv.window(args.seconds)
        t0 = time.perf_counter()
        checks = drv.check(drv.answers(), prec)
        program[seed] = dict(worst(checks), calls=res["calls"],
                             checked=len(checks), **res["e2e"])
        run.say(f"program seed {seed}: {program[seed]} "
                f"(check {time.perf_counter() - t0:.1f}s)")
    for seed in _seeds(args.control_seeds):
        drv.draw(seed)
        t0 = time.perf_counter()
        control[seed] = controls(drv, cfg, prec)
        run.say(f"control seed {seed}: {control[seed]} "
                f"({time.perf_counter() - t0:.1f}s)")
    limits = registry.load_limits(cell["name"])
    names = sorted({c for by in control.values() for c in by})
    summary = {}
    for k in limits:
        lower = max(p[k] for p in program.values())
        by_control = {c: min(by[c][k] for by in control.values())
                      for c in names}
        # a control gives an upper reading only at three times the lower
        uppers = [u for u in by_control.values()
                  if u >= 3 * lower and u > lower]
        summary[k] = {"lower": lower, "upper": min(uppers, default=None),
                      "upper_by_control": by_control}
    # each control has to fail some number on every seed
    summary["control_fails_limits"] = {
        c: all(any(by[c][k] > limits[k] for k in limits)
               for by in control.values()) for c in names}
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": cell["name"], "program": program,
                   "control": control, "summary": summary}, f, indent=1)
    print(json.dumps({"workload": cell["name"], "summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
