"""Distributed PackSELL quickstart: partitioned SpMV + multi-device PCG.

Run with simulated host devices (the device count must be set before JAX
initializes — do it on the command line, not in code):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python examples/distributed_pcg.py

The flow is the whole distributed story in four lines:

    dplan = build_dist_plan(a, codec="fp16")       # partition + halo maps
    y     = dplan.spmv(x)                          # one shard_map dispatch
    x, info = cg.jacobi_pcg_dist(dplan, a.diagonal(), b)   # sharded solve

Everything else below is verification and reporting.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

jax.config.update("jax_enable_x64", True)

from repro.core import packsell, testmats                     # noqa: E402
from repro.distributed import build_dist_plan                 # noqa: E402
from repro.launch.compile_cache import use_compile_cache     # noqa: E402
from repro.solvers import cg                                  # noqa: E402
from repro.solvers import operators as op                     # noqa: E402


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--side", type=int, default=10,
                    help="HPCG grid side (n = side^3 rows)")
    ap.add_argument("--codec", default="fp16",
                    help="value codec: fp16 | bf16 | e8m | fixed<F>")
    ap.add_argument("--dwidth", type=int, default=15, help="delta width D")
    ap.add_argument("--tol", type=float, default=1e-7)
    args = ap.parse_args()

    n_dev = jax.device_count()
    print(f"devices: {n_dev} ({jax.default_backend()})")

    a = testmats.hpcg(args.side, args.side, args.side)
    s, _ = op.sym_scale(a)
    n = s.shape[0]
    print(f"matrix: HPCG {args.side}^3 -> n={n}, nnz={s.nnz}")

    # one shard per device: row-block partition, per-partition σ-sort,
    # halo maps, jitted shard_map dispatch
    dplan = build_dist_plan(s, C=32, sigma=256, D=args.dwidth,
                            codec=args.codec)
    st = dplan.memory_stats()
    print(f"shards: {dplan.n_shards}, halo entries: {st['halo_entries']} "
          f"({st['halo_entries'] / max(n, 1):.1%} of x), "
          f"bytes/shard: {st['min_shard_bytes']}..{st['max_shard_bytes']}")

    # distributed SpMV matches the single-device plan engine
    rng = np.random.default_rng(0)
    x = rng.standard_normal(n).astype(np.float32)
    y_dist = np.asarray(dplan.spmv(x))
    mat = packsell.from_csr(s, C=32, sigma=256, D=args.dwidth,
                            codec=args.codec)
    y_one = np.asarray(packsell.packsell_spmv_jnp(mat, jnp.asarray(x)))
    err = np.max(np.abs(y_dist - y_one)) / max(np.max(np.abs(y_one)), 1e-30)
    print(f"spmv max rel diff vs single device: {err:.2e}")
    assert err < 1e-5, "distributed SpMV diverged from single device"

    # distributed Jacobi-PCG: whole solve inside one shard_map region
    b = jnp.asarray(rng.standard_normal(n))
    x_sol, info = cg.jacobi_pcg_dist(dplan, s.diagonal(), b, tol=args.tol,
                                     maxiter=500, dtype=jnp.float64)
    r = np.asarray(b, np.float64) - s @ np.asarray(x_sol, np.float64)
    true_res = np.linalg.norm(r) / np.linalg.norm(np.asarray(b))
    print(f"pcg: {int(info.iters)} iters, recurrence relres "
          f"{float(info.relres):.2e}, true relres {true_res:.2e} "
          f"(floors at the {args.codec} quantization error)")
    assert float(info.relres) < args.tol, "PCG did not converge"
    print("OK")


if __name__ == "__main__":
    main()
