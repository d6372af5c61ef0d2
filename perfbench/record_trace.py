"""Record the small chip traces that ``tests/test_perfbench_trace.py``
reduces, and describe what they hold.

    python3 perfbench/record_trace.py --out chiprun_out/trace_probe

On a TPU, at a 16^3 HPCG grid: a few synced fp16 SpMV calls, and one
3-iteration PCG set, each under its own profiler trace, through the same
traffic kinds and profiler options as ``run.py``. Writes ``<name>.xplane.pb``,
the compiled HLO the window ran (``<name>.hlo.txt``) and
``structure.json`` (planes, lines, event counts and the first events of
each line with their stats) to ``--out``.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))


def _describe(path: str) -> list:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    out = []
    for plane in pd.planes:
        lines = []
        for line in plane.lines:
            evs = list(line.events)
            lines.append({"name": line.name, "events": len(evs), "first": [
                {"name": e.name, "start_ns": e.start_ns,
                 "duration_ns": e.duration_ns,
                 "stats": {k: str(v)[:300] for k, v in e.stats}}
                for e in evs[:4]]})
        out.append({"plane": plane.name,
                    "stats": {k: str(v)[:200] for k, v in plane.stats},
                    "lines": lines})
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--side", type=int, default=16)
    args = ap.parse_args()
    import jax

    from perfbench import registry, run

    run._configure_jax()
    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1
    bench = registry.load_benchmark()
    cfg = registry.load_config(bench, "hpcg104_fp16")
    cfg["matrix"].update(nx=args.side, ny=args.side, nz=args.side)
    os.makedirs(args.out, exist_ok=True)
    structure = {}
    for name, mix, seconds in (
            ("spmv16", registry.load_traffic("spmv"), 0.02),
            ("pcg16", dict(registry.load_traffic("pcg50"),
                           iters_per_set=3), 0.0)):
        drv = registry.load_kind(mix["kind"])(cfg, mix, 11, run.say)
        drv.setup()
        tdir = tempfile.mkdtemp(prefix="perfbench-probe-")
        jax.profiler.start_trace(tdir, profiler_options=run._trace_options())
        res = drv.window(seconds)
        jax.profiler.stop_trace()
        src = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                     "*.xplane.pb"))[0]
        dst = os.path.join(args.out, f"{name}.xplane.pb")
        shutil.copy(src, dst)
        shutil.rmtree(tdir, ignore_errors=True)
        structure[name] = {"calls": res["calls"],
                           "bytes": os.path.getsize(dst),
                           "planes": _describe(dst)}
        with open(os.path.join(args.out, f"{name}.hlo.txt"), "w") as f:
            f.write("\n".join(drv.hlo_texts()))
    with open(os.path.join(args.out, "structure.json"), "w") as f:
        json.dump(structure, f, indent=1)
    print(json.dumps({k: {"calls": v["calls"], "bytes": v["bytes"]}
                      for k, v in structure.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
