"""The paper's solver scenario (§5.2, §6) with adaptive precision.

The codec is no longer hard-coded: ``repro.precision.select`` picks the
``(codec, D)`` split for an error budget, and ``solvers.cg.adaptive_pcg``
runs the mixed-precision PCG recipe end-to-end — low-precision inner
solves, residual-stagnation detection, codec-tier promotion mid-solve.
Also prints the Fig. 12-style IO-CG / F3R convergence comparison.

    PYTHONPATH=src python examples/mixed_precision_solver.py [--nx 10]
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.core import testmats                             # noqa: E402
from repro.launch.compile_cache import use_compile_cache    # noqa: E402
from repro.solvers import cg, f3r, iocg                     # noqa: E402
from repro.solvers.operators import OperatorSet, sym_scale  # noqa: E402


def true_relres(a, x, b):
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(b - a @ np.asarray(x, np.float64))
                 / np.linalg.norm(b))


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--nx", type=int, default=10)
    ap.add_argument("--budget", type=float, default=1e-3,
                    help="SpMV error budget handed to precision.select")
    args = ap.parse_args()

    a0 = testmats.hpcg(args.nx, args.nx, args.nx)
    a, _ = sym_scale(a0)
    ops = OperatorSet(a, C=32, sigma=256)
    n = a.shape[0]
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.random(n))              # paper: U[0,1) rhs
    print(f"HPCG {args.nx}^3: n={n}, nnz={a.nnz}\n")

    print(f"--- adaptive PCG (precision.select, budget={args.budget:g}) ---")
    plan = ops.precision_plan(args.budget)
    sel = next((c for c in plan.rationale["candidates"]
                if c["decision"].startswith("selected")), None)
    if sel is None:
        print(f"selected {plan.primary.label}: no packed codec fits the "
              f"budget ({plan.rationale.get('fallback', 'fp32 fallback')})")
    else:
        print(f"selected {plan.primary.label}:"
              f" probe_err={sel['probe_err']:.2e}"
              f" model_err={sel['model_err']:.2e}"
              f" bytes/nnz={sel['bytes_per_nnz']:.2f}")
    diag = ops.diag()
    dinv = jnp.asarray(np.where(diag == 0, 1.0, 1.0 / diag))
    M = lambda r: r * dinv                                   # noqa: E731

    x, info = cg.pcg(ops.matvec("fp64"), b, M=M, tol=1e-8, maxiter=1000,
                     dtype=jnp.float64)
    print(f"{'PCG (FP64 baseline)':28s} iters={int(info.iters):4d} "
          f"true relres={true_relres(a, x, b):.2e}")

    tiers, labels, sub32, hi = ops.adaptive_tiers(args.budget)
    x, ainfo = cg.adaptive_pcg(tiers, b, M=M, matvec_hi=hi, tol=1e-8,
                               maxiter=60, m_in=16, dtype=jnp.float64)
    counts = np.asarray(ainfo.tier_matvecs)
    total = counts.sum() + int(ainfo.hi_matvecs)
    frac = counts[np.asarray(sub32)].sum() / max(total, 1)
    print(f"{'adaptive PCG (' + labels[0] + ')':28s} "
          f"outer={int(ainfo.iters):4d} "
          f"true relres={true_relres(a, x, b):.2e} "
          f"promotions={int(ainfo.promotions)} "
          f"sub-32-bit matvecs={frac:.0%}")

    print("\n--- IO-CG (outer FP64 FCG + m_in=20 inner PCG) ---")
    x, info = iocg.pcg_reference(ops, b)
    print(f"{'PCG (FP64 baseline)':28s} iters={int(info.iters):4d} "
          f"true relres={true_relres(a, x, b):.2e}")
    for v in ("fp64", "fp32", "fp16", "e8m8"):
        cfg = iocg.variant(v, m_in=20)
        x, info = iocg.solve(ops, b, cfg)
        label = {"e8m8": "E8M14 (PackSELL)"}.get(v, v.upper())
        print(f"{'IO-CG ' + label:28s} outer={int(info.iters):4d} "
              f"true relres={true_relres(a, x, b):.2e}")

    print("\n--- F3R (nested FGMRES x3 + Richardson) ---")
    for v in ("fp64", "fp16", "packsell"):
        cfg = f3r.presets(v)
        x, info = f3r.solve(ops, b, cfg)
        label = {"fp64": "FP64-F3R", "fp16": "FP16-F3R (SELL)",
                 "packsell": "PackSELL-F3R"}[v]
        print(f"{label:28s} cycles={int(info.iters):4d} "
              f"true relres={true_relres(a, x, b):.2e}")
    print("\nFP16-F3R and PackSELL-F3R must show identical cycle counts "
          "(the paper's identical-convergence claim).")


if __name__ == "__main__":
    main()
