"""Distributed SpMV / PCG scaling over simulated host devices (DESIGN.md §7).

Strong scaling: one fixed matrix partitioned over 1/2/4/8 shards; weak
scaling: per-shard problem size held constant while the fleet grows. Both
sweep the two halo-exchange modes and record the distributed Jacobi-PCG
(time, iterations — iteration counts must not drift with the shard count).

Runs in the calling process over the devices it sees; shard counts above
the device count are skipped. On the CPU (``JAX_PLATFORMS=cpu``) the entry
points give the process 8 host devices before JAX starts
(``common.cpu_host_devices``). Simulated host devices share one CPU: the
curves measure dispatch + partition overheads and communication-volume
effects, not real interconnect bandwidth (DESIGN.md §2.5's relative-
instrument caveat applies doubly here).

Writes ``BENCH_distributed.json`` at the repo root, next to
``BENCH_spmv.json``.
"""
from __future__ import annotations

import os

N_DEV = 8
SHARD_COUNTS = (1, 2, 4, 8)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_JSON_PATH = os.environ.get("REPRO_BENCH_DIST_JSON",
                            os.path.join(_ROOT, "BENCH_distributed.json"))


def _suite(scale: str):
    from repro.core import testmats
    if scale == "tiny":
        return testmats.hpcg(8, 8, 8), 6, (1e-5, 50)
    if scale == "small":
        return testmats.hpcg(16, 16, 16), 12, (1e-6, 200)
    return testmats.hpcg(24, 24, 24), 16, (1e-6, 200)     # medium


def run(scale: str | None = None) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import testmats
    from repro.distributed import build_dist_plan
    from repro.solvers import cg
    from repro.solvers import operators as op

    from . import common

    scale = scale or common.SCALE
    first_row = len(common.rows())
    ndev = jax.device_count()
    a_strong, weak_side, (tol, maxiter) = _suite(scale)
    s_strong, _ = op.sym_scale(a_strong)
    rng = np.random.default_rng(0)
    x = rng.standard_normal(s_strong.shape[0]).astype(np.float32)
    b = jnp.asarray(rng.standard_normal(s_strong.shape[0]))

    base_t = {}
    for P in SHARD_COUNTS:
        if P > ndev:
            continue
        for mode in ("ppermute", "all_gather"):
            dplan = build_dist_plan(s_strong, P, C=32, sigma=256, D=15,
                                    codec="fp16", exchange=mode)
            xs = dplan.shard_vector(x)
            t = common.time_fn(
                lambda xs=xs, dp=dplan, m=mode: dp.spmv_sharded(xs, mode=m),
                warmup=2, repeats=5)
            st = dplan.memory_stats()
            key = ("spmv", mode)
            base_t.setdefault(key, t)
            common.emit(
                "dist_strong_spmv", f"hpcg_p{P}_{mode}", shards=P,
                n=s_strong.shape[0], nnz=int(s_strong.nnz), t_spmv_s=t,
                speedup_vs_p1=base_t[key] / t,
                halo_entries=st["halo_entries"], h_pad=st["h_pad"])
            if mode == "ppermute":
                _, info = cg.jacobi_pcg_dist(dplan, s_strong.diagonal(), b,
                                             tol=tol, maxiter=maxiter,
                                             dtype=jnp.float64)
                t_pcg = common.time_fn(
                    lambda dp=dplan: cg.jacobi_pcg_dist(
                        dp, s_strong.diagonal(), b, tol=tol,
                        maxiter=maxiter, dtype=jnp.float64)[0],
                    warmup=1, repeats=3)
                key = ("pcg",)
                base_t.setdefault(key, t_pcg)
                common.emit(
                    "dist_strong_pcg", f"hpcg_p{P}", shards=P,
                    iters=int(info.iters), relres=float(info.relres),
                    t_solve_s=t_pcg, speedup_vs_p1=base_t[key] / t_pcg)

    # weak scaling: ~weak_side^3 rows per shard
    for P in SHARD_COUNTS:
        if P > ndev:
            continue
        a_w = testmats.hpcg(weak_side, weak_side, weak_side * P)
        s_w, _ = op.sym_scale(a_w)
        xw = np.random.default_rng(1).standard_normal(
            s_w.shape[0]).astype(np.float32)
        dplan = build_dist_plan(s_w, P, C=32, sigma=256, D=15, codec="fp16")
        xs = dplan.shard_vector(xw)
        t = common.time_fn(lambda xs=xs, dp=dplan: dp.spmv_sharded(xs),
                           warmup=2, repeats=5)
        base_t.setdefault("weak", t)
        common.emit(
            "dist_weak_spmv", f"hpcg_p{P}", shards=P, n=s_w.shape[0],
            nnz=int(s_w.nnz), t_spmv_s=t,
            efficiency_vs_p1=base_t["weak"] / t)

    payload = dict(
        scale=scale, backend=jax.default_backend(), devices=ndev,
        note=("simulated host devices share one CPU: curves measure "
              "dispatch/partition overhead and communication volume, not "
              "interconnect bandwidth; speedup_vs_p1 = t(P=1)/t(P)"),
        rows=common.rows()[first_row:],
    )
    common.save_bench_json(_JSON_PATH, payload)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default=None)
    args = ap.parse_args()
    from . import common
    common.cpu_host_devices(N_DEV)
    run(args.scale)
