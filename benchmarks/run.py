"""Benchmark harness entry point — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only spmv,e8my] [--scale small]

Output: CSV lines ``bench,case,k=v,...`` plus artifacts/bench_results.json.
Scales: tiny (CI), small (default), medium. Every module runs in this one
process; with ``JAX_PLATFORMS=cpu`` the multi-device modules get 8 host
devices (:func:`common.cpu_host_devices`).
"""
from __future__ import annotations

import argparse
import sys
import time

from repro.launch.compile_cache import use_compile_cache

from . import common

MODULES = ("spmv", "memory", "e8my", "f3r", "iocg", "kernels", "roofline",
           "distributed", "precision", "composite", "robust", "serving")
MULTI_DEVICE = ("distributed", "composite")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list from: " + ",".join(MODULES))
    ap.add_argument("--scale", default=common.SCALE)
    ap.add_argument("--out", default="artifacts/bench_results.json")
    args = ap.parse_args()
    only = args.only.split(",") if args.only else list(MODULES)
    if set(only) & set(MULTI_DEVICE):
        common.cpu_host_devices(8)
    use_compile_cache()

    t0 = time.time()
    failures = []
    for name in only:
        mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
        print(f"### bench_{name} (scale={args.scale})", flush=True)
        t1 = time.time()
        try:
            mod.run(args.scale)
        except Exception as e:  # noqa: BLE001 — report, continue the suite
            failures.append((name, repr(e)))
            print(f"[FAIL] bench_{name}: {e!r}", flush=True)
        print(f"### bench_{name} done in {time.time() - t1:.1f}s", flush=True)
    common.save_rows(args.out)
    print(f"[benchmarks] total {time.time() - t0:.1f}s, "
          f"{len(failures)} failures")
    if failures:
        for name, err in failures:
            print(f"  FAILED {name}: {err}")
        sys.exit(1)


if __name__ == "__main__":
    main()
