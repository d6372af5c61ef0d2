"""Shared benchmark utilities: timing, CSV emission, result collection.

Wall-clock on this CPU container is a *relative* instrument (DESIGN.md §2):
every figure reports PackSELL against the SELL/CSR baselines timed the same
way, mirroring how the paper reports speedups rather than absolute device
FLOPS. Roofline-based absolute analysis lives in EXPERIMENTS.md §Roofline.
"""
from __future__ import annotations

import json
import os
import time

import jax
import numpy as np

SCALE = os.environ.get("REPRO_BENCH_SCALE", "small")   # tiny|small|medium

#: BENCH_*.json metadata-header schema. Bump when header fields change
#: meaning — trajectory tooling compares runs only within a schema version.
BENCH_SCHEMA_VERSION = 1

_ROWS: list[dict] = []


def cpu_host_devices(n: int) -> None:
    """CPU rehearsal of the multi-device benchmarks: with
    ``JAX_PLATFORMS=cpu``, give this process ``n`` host devices. XLA reads
    the flag when the backend starts, so entry points call this before
    anything touches a device. Anywhere else (a chip host) it does nothing:
    the benchmarks run over the devices there are, in this one process."""
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}").strip()


def bench_meta(**extra) -> dict:
    """Schema-versioned metadata header stamped into every BENCH_*.json.
    Provenance fields (commit, toolchain, machine) come from
    ``observe.export.run_meta`` — the SAME header telemetry archives
    carry, so bench files and metric streams stay joinable in the
    trajectory store."""
    from repro.observe import export as _export

    return {
        "schema_version": BENCH_SCHEMA_VERSION,
        **_export.run_meta(scale=SCALE, **extra),
    }


def save_bench_json(path: str, payload) -> None:
    """Write a checked-in BENCH_*.json with the :func:`bench_meta` header.
    ``payload`` may be a dict (header merged in under ``meta``) or a bare
    row list (wrapped as ``{"meta": ..., "rows": [...]}``).

    Every writer gets the flight-recorder treatment for free: unless the
    payload already carries an ``observe_report`` section, the current
    ``observe.report()`` snapshot is embedded (counters land next to the
    timings they describe), and the full telemetry state is archived as a
    JSONL delta under ``artifacts/obs/`` (``REPRO_OBS_ARCHIVE_DIR``; set
    to empty to disable)."""
    if not isinstance(payload, dict):
        payload = {"rows": payload}
    meta = bench_meta()
    if "observe_report" not in payload:
        try:
            from repro import observe as _observe

            payload = {**payload, "observe_report": _observe.report()}
        except Exception:
            pass
    payload = {"meta": meta, **payload}
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
    print(f"[benchmarks] wrote {path}")
    _archive_telemetry(path, meta)


def _archive_telemetry(bench_path: str, meta: dict) -> None:
    """Append this run's metric state to ``artifacts/obs/<bench>.jsonl``
    (one meta header per file, then snapshot-deltas — JsonlSink
    semantics), so the raw counters behind every committed BENCH figure
    survive next to the repo's other artifacts."""
    root = os.environ.get(
        "REPRO_OBS_ARCHIVE_DIR",
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "artifacts", "obs"))
    if not root:
        return
    try:
        from repro.observe import export as _export

        stem = os.path.splitext(os.path.basename(bench_path))[0]
        sink = _export.JsonlSink(
            os.path.join(root, f"{stem}.jsonl"),
            meta={**meta, "bench_file": os.path.basename(bench_path)})
        sink.flush()
    except Exception as e:            # archive must never fail the bench
        print(f"[benchmarks] telemetry archive skipped: {e!r}")


def time_fn(fn, *args, warmup: int = 2, repeats: int = 5) -> float:
    """Median seconds per call of a jit-compatible fn (blocks on result)."""
    for _ in range(warmup):
        out = fn(*args)
        jax.block_until_ready(out)
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def time_fns(fns: dict, args: dict, *, warmup: int = 2,
             rounds: int = 9, samples: bool = False) -> dict:
    """Interleaved timings: one call of every fn per round (order rotated
    per round), so contended / throttled containers perturb all candidates
    alike. Returns per-fn medians; ``samples=True`` returns the raw
    per-round lists instead, for PAIRED statistics — e.g.
    :func:`paired_speedup`, the comparison instrument behind
    fused-vs-cursor in BENCH_spmv.json."""
    keys = list(fns)
    ts = {k: [] for k in keys}
    for k in keys:
        for _ in range(warmup):
            jax.block_until_ready(fns[k](*args[k]))
    for r in range(rounds):
        order = keys[r % len(keys):] + keys[:r % len(keys)]
        for k in order:
            t0 = time.perf_counter()
            jax.block_until_ready(fns[k](*args[k]))
            ts[k].append(time.perf_counter() - t0)
    if samples:
        return ts
    return {k: float(np.median(v)) for k, v in ts.items()}


def paired_speedup(ts: dict, base: str, cand: str) -> float:
    """Median of per-round ``t_base / t_cand`` ratios from
    :func:`time_fns(..., samples=True)`. Pairing cancels the machine's
    between-round throughput drift that poisons unpaired medians on a
    shared container."""
    return float(np.median(np.asarray(ts[base]) / np.asarray(ts[cand])))


def emit(bench: str, case: str, **fields):
    row = {"bench": bench, "case": case, **fields}
    _ROWS.append(row)
    kv = ",".join(f"{k}={_fmt(v)}" for k, v in fields.items())
    print(f"{bench},{case},{kv}", flush=True)
    return row


def _fmt(v):
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def rows() -> list[dict]:
    return _ROWS


def save_rows(path: str):
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(_ROWS, f, indent=1, default=float)
    print(f"[benchmarks] wrote {len(_ROWS)} rows -> {path}")


def backward_error(y, a_csr, x) -> float:
    """Paper eq. (5): ||y - Ax||_inf / (||A||_inf ||x||_inf)."""
    y = np.asarray(y, np.float64)
    x = np.asarray(x, np.float64)
    exact = a_csr.astype(np.float64) @ x
    num = np.max(np.abs(y - exact))
    anorm = np.max(np.abs(a_csr).sum(axis=1))
    xnorm = np.max(np.abs(x))
    return float(num / max(anorm * xnorm, 1e-300))
