"""Run one benchmark cell once and print its result as one JSON line.

    python3 perfbench/run.py --workload hpcg104.spmv.fp16 --seed 7 \\
        --seconds 51 --trace 0

The cell, its configuration, traffic mix and metrics are found by name
through ``BENCHMARK.json`` (see ``registry.py``). One process: it builds
the matrix, packs and plans it with the program, warms up, measures for
``--seconds`` and then checks the window's answers against the plain
reference (``reference.py``). ``--trace 1`` runs the same window under the
JAX profiler and reports the per-layer metrics instead of the end-to-end
ones. The run fails, and prints no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

class RunFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"perfbench: {msg}", flush=True)


def _args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def _configure_jax():
    import jax

    jax.config.update("jax_enable_x64", True)     # float64 solver vectors
    # every program goes to the persistent cache, however fast it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from repro.launch.compile_cache import use_compile_cache
    from repro.observe import metrics as obs

    # the program's named scopes (packsell.*) are planted only while its
    # recorder is on; on in every run, so both kinds run one program
    obs.enable(True)
    return use_compile_cache()


def _devices(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    say(f"platform={d.platform} device_kind={d.device_kind!r} "
        f"count={len(devs)}")
    if d.platform != "tpu":
        raise RunFailure(f"platform is {d.platform!r}, not 'tpu': JAX "
                         f"found no TPU")
    if len(devs) < chips:
        raise RunFailure(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs


class _CompileCounter:
    """Counts compile requests (persistent-cache hits included) while on."""

    def __init__(self):
        import jax.monitoring as mon

        self.on, self.n = False, 0

        def listen(event, **_):
            if self.on and event.endswith("compile_requests_use_cache"):
                self.n += 1

        def listen_dur(event, _secs, **_):
            if self.on and event.endswith("backend_compile_duration"):
                self.n += 1

        mon.register_event_listener(listen)
        mon.register_event_duration_secs_listener(listen_dur)


def _trace_options():
    """Host spans (``TraceAnnotation``) and device ops; no Python tracer,
    which would time every Python call of the window."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _memory_peak(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def run(argv=None) -> dict:
    from perfbench import registry, trace as trace_mod

    args = _args(argv)
    bench = registry.load_benchmark()
    cell = registry.workload(bench, args.workload)
    cfg = registry.load_config(bench, cell["config"])
    mix = registry.load_traffic(cell["traffic"])
    limits = registry.load_limits(cell["name"])
    kind = registry.load_kind(mix["kind"])
    try:
        import jax
        cache = _configure_jax()
    except ImportError as e:
        raise RunFailure(f"cannot import JAX or the program: {e}") from e
    devs = _devices(int(cell["chips"]))
    say(f"compile cache {cache}")

    drv = kind(cfg, mix, args.seed, say)
    op = drv.setup()
    # set-up's objects stay out of the window's garbage collections (a
    # full collection over about 140 k objects takes about 0.1 s)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    say(f"setup {setup_s:.3f}s (build {op['build_s']:.3f}s)")

    counter = _CompileCounter()
    tdir = tempfile.mkdtemp(prefix="perfbench-trace-") if args.trace \
        else None
    try:
        if tdir:
            jax.profiler.start_trace(tdir, profiler_options=_trace_options())
        counter.on = True
        try:
            res = drv.window(args.seconds)
        finally:
            counter.on = False
            if tdir:
                jax.profiler.stop_trace()
        say(f"window {res['window_s']:.3f}s, {res['calls']} calls, "
            f"{counter.n} compile requests inside it; slowest calls "
            f"[index, ms] {res['slowest']}")
        summary = trace_mod.summarize(trace_mod.find(tdir),
                                      drv.hlo_texts()) if tdir else None
    finally:
        if tdir:
            shutil.rmtree(tdir, ignore_errors=True)
    mem_peak = _memory_peak(devs[0])

    answers = drv.answers()
    drv.free()
    t0 = time.perf_counter()
    checks = drv.check(answers, cfg["precision"]["values"])
    say(f"reference check of {len(checks)} answers "
        f"{time.perf_counter() - t0:.3f}s")
    failed = sum(any(not c[k] <= limits[k] for k in c) for c in checks)
    # a gap that is not finite reads as the largest float, so the line
    # stays plain JSON
    worst = {k: min(max(c[k] for c in checks), sys.float_info.max)
             for k in checks[0]}
    correct = failed == 0 and counter.n == 0 and all(
        k in worst for k in limits)

    d = devs[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(devs), "memory_peak_bytes": mem_peak}
    metrics = {}
    out = {"correct": correct, "attempted": res["calls"], "failed": failed}
    if args.trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        ctx = {"kind": mix["kind"], "trace": summary,
               "build_s": op["build_s"], "window": res,
               "shape": op["a"].shape, "nnz": int(op["a"].nnz),
               "peak": registry.peak(d.device_kind)}
        for m in registry.metrics_for(bench["per_layer"], cell["name"]):
            v = registry.load_metric(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(res["e2e"], setup_s=setup_s)
        for m in registry.metrics_for(bench["end_to_end"], cell["name"]):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    if args.trace:
        out["breakdown"] = summary.breakdown()
    out["checks"] = {k: {"value": worst[k], "limit": limits[k]}
                     for k in limits}
    out["checks"]["compiles_in_window"] = {"value": counter.n, "limit": 0}
    return out


def main(argv=None) -> int:
    try:
        out = run(argv)
    except RunFailure as e:
        print(f"perfbench: FAIL: {e}", file=sys.stderr)
        return 1
    for k, v in out["checks"].items():
        print(f"check {k} = {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
