"""Distributed mixed-precision PCG vs distributed fp32 PCG (DESIGN.md §9).

The composition the CompositePlan refactor unlocks: the SAME matrix solved
on 2–8 simulated devices by (a) ``cg.jacobi_pcg_dist`` over an
uncompressed fp32 member set and (b) ``cg.adaptive_pcg_dist`` over the
budget-selected codec tier ladder (sub-32-bit inner matvecs, fp64
true-residual outer steps, tier promotion on stagnation). Records solve
time, iteration counts (must not drift with the shard count), the
sub-32-bit matvec fraction, and the dist-mixed vs dist-fp32 speedup.

Runs in the calling process over the devices it sees, as
``bench_distributed`` does (on the CPU, 8 host devices via
``common.cpu_host_devices``; DESIGN.md §2.5's relative-instrument caveat
applies doubly on simulated devices).

Writes ``BENCH_composite.json`` at the repo root.
"""
from __future__ import annotations

import os

N_DEV = 8
SHARD_COUNTS = (2, 4, 8)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_JSON_PATH = os.environ.get("REPRO_BENCH_COMPOSITE_JSON",
                            os.path.join(_ROOT, "BENCH_composite.json"))


def _suite(scale: str):
    from repro.core import testmats
    if scale == "tiny":
        return testmats.hpcg(6, 6, 6), (1e-8, 40, 8)
    if scale == "small":
        return testmats.hpcg(12, 12, 12), (1e-8, 60, 16)
    return testmats.hpcg(16, 16, 16), (1e-8, 60, 16)      # medium


def run(scale: str | None = None) -> None:
    import jax
    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp
    import numpy as np

    from repro.solvers import cg
    from repro.solvers import operators as op

    from . import common

    scale = scale or common.SCALE
    first_row = len(common.rows())
    ndev = jax.device_count()
    a, (tol, maxiter, m_in) = _suite(scale)
    s, _ = op.sym_scale(a)
    n = s.shape[0]
    d = s.diagonal()
    budget = 1e-3
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal(n))

    ops = op.OperatorSet(s, C=32, sigma=64)
    plan = ops.precision_plan(budget)
    for P in SHARD_COUNTS:
        if P > ndev:
            continue
        from repro.distributed import build_dist_plan
        dp32 = build_dist_plan(s, P, C=32, sigma=64,
                               classes=[("fp32", 0, None)])
        _, i32 = cg.jacobi_pcg_dist(dp32, d, b, tol=tol, maxiter=400,
                                    dtype=jnp.float64)
        t32 = common.time_fn(
            lambda dp=dp32: cg.jacobi_pcg_dist(
                dp, d, b, tol=tol, maxiter=400, dtype=jnp.float64)[0],
            warmup=1, repeats=3)

        ladder = ops.dist_adaptive_tiers(budget, n_shards=P)
        xm, im = cg.adaptive_pcg_dist(ladder, d, b, tol=tol,
                                      maxiter=maxiter, m_in=m_in,
                                      dtype=jnp.float64)
        tm = common.time_fn(
            lambda la=ladder: cg.adaptive_pcg_dist(
                la, d, b, tol=tol, maxiter=maxiter, m_in=m_in,
                dtype=jnp.float64)[0],
            warmup=1, repeats=3)
        mv = np.asarray(im.tier_matvecs)
        sub32_frac = float(mv[np.asarray(ladder.sub32)].sum()
                           / max(mv.sum(), 1))
        r = np.asarray(s @ np.asarray(xm, np.float64)) - np.asarray(
            b, np.float64)
        common.emit(
            "dist_mixed_pcg", f"hpcg_p{P}", shards=P, n=n,
            nnz=int(s.nnz), budget=budget,
            primary=plan.primary.label, tiers=len(ladder.labels),
            fp32_iters=int(i32.iters), fp32_t_s=t32,
            mixed_outer_iters=int(im.iters),
            mixed_promotions=int(im.promotions),
            mixed_sub32_frac=sub32_frac,
            mixed_true_relres=float(np.linalg.norm(r)
                                    / np.linalg.norm(np.asarray(b))),
            mixed_t_s=tm, speedup_mixed_vs_fp32=t32 / tm)

    payload = dict(
        scale=scale, backend=jax.default_backend(), devices=ndev,
        note=("dist-mixed adaptive_pcg_dist vs dist-fp32 jacobi_pcg_dist "
              "on simulated host devices sharing one CPU: wall times "
              "measure dispatch + word-stream-volume effects, not real "
              "interconnect bandwidth; iteration counts are the invariant "
              "to watch (must not drift with P)"),
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
