"""Chip smoke test: PackSELL SpMV and PCG on a TPU at HPCG size.

    python chip_smoke.py              # one chip: the 104^3 HPCG stencil
    python chip_smoke.py --chips 4    # four chips: distributed SpMV + PCG

One chip runs the main path through the public API — CSR ->
``packsell.from_csr`` -> ``plan.get_plan`` -> SpMV (fp16/D15, e8m/D8),
``cg.jacobi_pcg_stored`` to 1e-6 and the mixed-precision
``cg.adaptive_pcg`` to 1e-8 — on HPCG's default local grid (104^3, from
``hpcg.dat`` of the HPCG reference code) and checks every result against
scipy in fp64. ``--chips 4`` runs only the distributed path on 104x104x416
(one 104^3 share per chip) and checks it against the single-device plan.

Everything runs in this one process. The run fails (nonzero exit, no result
line) when JAX finds no TPU, when a plan would run Pallas in interpret
mode, or when any check fails. The last line of standard output is one
JSON object naming the device. Times printed here are smoke timings, not
benchmark results.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

SIDE = 104                  # HPCG default local grid (hpcg.dat: 104 104 104)
C, SIGMA = 32, 256
SEED = 0
TIMED_CALLS = 20
#: only the chip counts; a rehearsal on the CPU clears this
REQUIRE_TPU = True


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def check_device(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    say(f"platform={d.platform} device_kind={d.device_kind!r} "
        f"device_count={len(devs)}")
    if REQUIRE_TPU:
        check(d.platform == "tpu",
              f"platform is {d.platform!r}, not 'tpu': JAX found no TPU")
        check(len(devs) >= chips,
              f"--chips {chips} needs {chips} devices, found {len(devs)}")
    return devs


def check_plan(label: str, plan) -> None:
    say(f"plan[{label}] variant={plan.variant} interpret={plan.interpret} "
        f"cache_mode={plan.cache_mode} policy={plan.policy!r}")
    if REQUIRE_TPU:
        check(not plan.interpret,
              f"plan[{label}] reports interpret=True (Pallas interpret mode)")


def timed(fn, *args):
    """(result, seconds) of one call that ends in block_until_ready."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args))
    return out, time.perf_counter() - t0


def hpcg_matrix(nx: int, ny: int, nz: int):
    from repro.core import testmats
    from repro.solvers import operators as op

    t0 = time.perf_counter()
    s, _ = op.sym_scale(testmats.hpcg(nx, ny, nz))
    say(f"matrix HPCG {nx}x{ny}x{nz} (sym-scaled): n={s.shape[0]} "
        f"nnz={s.nnz} built in {time.perf_counter() - t0:.1f}s (host)")
    return s


def quantized(s, codec: str, D: int):
    """The operator the packed matrix stores: values through the codec."""
    import numpy as np

    from repro.core import codecs as cd

    aq = s.copy()
    aq.data = cd.quantize_np(s.data.astype(np.float32), cd.make_codec(codec),
                             D).astype(np.float64)
    return aq


def relres(a, x, b) -> float:
    import numpy as np

    x = np.asarray(x, np.float64)
    return float(np.linalg.norm(b - a @ x) / np.linalg.norm(b))


def spmv_phase(s, stats, codec: str, D: int):
    """Pack, plan, one SpMV checked against scipy fp64, then a steady
    median. Returns ``(mat, plan)``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import packsell
    from repro.kernels import plan as kplan
    from repro.precision import analyze

    label = f"{codec}/D{D}"
    t0 = time.perf_counter()
    mat = packsell.from_csr(s, C=C, sigma=SIGMA, D=D, codec=codec)
    t_pack = time.perf_counter() - t0
    t0 = time.perf_counter()
    plan = kplan.get_plan(mat)
    t_plan = time.perf_counter() - t0
    say(f"spmv[{label}] pack {t_pack:.1f}s, plan {t_plan:.1f}s (host)")
    check_plan(label, plan)

    x = np.random.default_rng(SEED).standard_normal(s.shape[1]) \
        .astype(np.float32)
    xj = jnp.asarray(x)
    y, t_first = timed(plan.spmv, mat, xj)
    y = np.asarray(y, np.float64)
    check(y.shape == (s.shape[0],) and np.isfinite(y).all(),
          f"spmv[{label}] gave shape {y.shape} or non-finite values")
    # Per row, |y - A x| <= (u + k 2^-24) (|A| |x|): u the codec's
    # element-wise bound (precision.analyze error model), k 2^-24 the fp32
    # products and k-term accumulation.
    x64 = x.astype(np.float64)
    ref = s @ x64
    scale = abs(s) @ np.abs(x64)
    k = int(np.diff(s.indptr).max())
    bound = analyze.model_error(codec, D, stats) + (k + 1) * 2.0 ** -24
    err = float(np.linalg.norm(y - ref) / np.linalg.norm(scale))
    rel = float(np.linalg.norm(y - ref) / np.linalg.norm(ref))
    say(f"spmv[{label}] ||y-Ax||/|| |A||x| ||={err:.3e} (bound {bound:.3e}), "
        f"||y-Ax||/||Ax||={rel:.3e}")
    check(err <= bound, f"spmv[{label}] error {err:.3e} > bound {bound:.3e}")

    ts = [timed(plan.spmv, mat, xj)[1] for _ in range(TIMED_CALLS)]
    say(f"spmv[{label}] chip smoke timing, not a benchmark: first call "
        f"(compile + run) {t_first:.3f}s, steady median of {TIMED_CALLS} "
        f"{float(np.median(ts)) * 1e3:.3f} ms")
    return mat, plan


def jacobi_phase(s, mat, plan, b, *, tol: float = 1e-6,
                 maxiter: int = 5000):
    """``jacobi_pcg_stored`` on the fp16 plan. The solver sees the
    fp16-stored operator, so its true residual is recomputed against that
    operator (against the exact A it floors at the fp16 quantization)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.solvers import cg

    (x, info), t_solve = timed(lambda: cg.jacobi_pcg_stored(
        mat, plan, s.diagonal(), jnp.asarray(b), tol=tol, maxiter=maxiter,
        dtype=jnp.float64))
    iters = int(info.iters)
    x = np.asarray(x, np.float64)
    check(np.isfinite(x).all(), "jacobi_pcg_stored returned non-finite x")
    rq = relres(quantized(s, mat.codec_name, mat.D), x, b)
    ra = relres(s, x, b)
    say(f"jacobi_pcg_stored[{mat.codec_name}/D{mat.D}] iters={iters} "
        f"recurrence relres={float(info.relres):.3e} true relres (scipy "
        f"fp64) vs stored operator={rq:.3e}, vs exact A={ra:.3e}")
    say(f"jacobi_pcg_stored chip smoke timing, not a benchmark: first solve "
        f"(compile + run) {t_solve:.3f}s")
    check(iters < maxiter and rq <= tol,
          f"jacobi_pcg_stored: iters={iters}, true relres {rq:.3e} > {tol}")
    return iters


def adaptive_phase(s, b, *, budget: float = 1e-3, tol: float = 1e-8,
                   m_in: int = 64, maxiter: int = 60):
    """The paper's mixed-precision PCG: ``OperatorSet.adaptive_tiers``
    picks the codec ladder for the SpMV error budget, ``adaptive_pcg`` runs
    sub-32-bit inner solves with fp64 outer residuals."""
    import jax.numpy as jnp
    import numpy as np

    from repro.precision import select as psel
    from repro.solvers import cg
    from repro.solvers import operators as op

    t0 = time.perf_counter()
    ops = op.OperatorSet(s, C=C, sigma=SIGMA)
    mvs, labels, sub32, hi = ops.adaptive_tiers(budget)
    say(f"adaptive tiers for budget {budget:g}: {labels} "
        f"(sub-32-bit {sub32.tolist()}), built in "
        f"{time.perf_counter() - t0:.1f}s (host)")
    for c in psel.tier_ladder(ops.precision_plan(budget)):
        kind = psel.operator_kind(c)
        if kind.startswith("plan_"):
            check_plan(kind, ops.plan_pair(kind)[1])

    diag = s.diagonal()
    dinv = jnp.asarray(np.where(diag == 0, 1.0, 1.0 / diag))

    def solve():
        return cg.adaptive_pcg(mvs, jnp.asarray(b), M=lambda r: r * dinv,
                               matvec_hi=hi, tol=tol, maxiter=maxiter,
                               m_in=m_in, dtype=jnp.float64)

    (x, info), t_first = timed(solve)
    x = np.asarray(x, np.float64)
    check(np.isfinite(x).all(), "adaptive_pcg returned non-finite x")
    counts = np.asarray(info.tier_matvecs)
    share = counts[sub32].sum() / max(counts.sum() + int(info.hi_matvecs), 1)
    ra = relres(s, x, b)
    say(f"adaptive_pcg outer iters={int(info.iters)} (m_in={m_in}) "
        f"promotions={int(info.promotions)} tier matvecs={counts.tolist()} "
        f"fp64 matvecs={int(info.hi_matvecs)} sub-32-bit matvec share="
        f"{share:.3f} true relres (scipy fp64) vs exact A={ra:.3e}")
    say(f"adaptive_pcg chip smoke timing, not a benchmark: first solve "
        f"(compile + run) {t_first:.3f}s")
    check(ra <= tol, f"adaptive_pcg true relres {ra:.3e} > {tol}")


def run_one_chip(side: int = SIDE) -> None:
    import numpy as np

    from repro.precision import analyze

    s = hpcg_matrix(side, side, side)
    stats = analyze.matrix_stats(s, sigma=SIGMA)
    mat16, plan16 = spmv_phase(s, stats, "fp16", 15)
    spmv_phase(s, stats, "e8m", 8)
    b = np.random.default_rng(SEED + 1).standard_normal(s.shape[0])
    jacobi_phase(s, mat16, plan16, b)
    adaptive_phase(s, b)


def shard_devices(dplan) -> list:
    """Device of each shard, read from the shardings of the plan's arrays;
    every array must put its shards on the same distinct devices."""
    import jax

    maps = set()
    for leaf in jax.tree.leaves(dplan.dev):
        shards = sorted(leaf.addressable_shards,
                        key=lambda sh: sh.index[0].start or 0)
        maps.add(tuple(sh.device for sh in shards))
    check(len(maps) == 1, f"plan arrays disagree on shard placement: {maps}")
    devs = list(maps.pop())
    check(len(devs) == dplan.n_shards and len(set(devs)) == len(devs),
          f"{dplan.n_shards} shards sit on devices {devs}, not on "
          f"{dplan.n_shards} distinct devices")
    return devs


def run_four_chips(side: int = SIDE, chips: int = 4) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core import packsell
    from repro.distributed import build_dist_plan
    from repro.kernels import plan as kplan
    from repro.parallel.sharding import make_shard_mesh
    from repro.solvers import cg

    s = hpcg_matrix(side, side, side * chips)
    t0 = time.perf_counter()
    dplan = build_dist_plan(s, C=C, sigma=SIGMA, D=15, codec="fp16",
                            mesh=make_shard_mesh(chips))
    say(f"dist plan: {dplan.n_shards} shards, exchange={dplan.exchange}, "
        f"built in {time.perf_counter() - t0:.1f}s (host)")
    part = dplan.ops.part
    for p, d in enumerate(shard_devices(dplan)):
        r0, r1 = part.rows_of(p)
        say(f"shard {p}: rows [{r0}, {r1}) on device id={d.id} {d}")

    mat = packsell.from_csr(s, C=C, sigma=SIGMA, D=15, codec="fp16")
    plan = kplan.get_plan(mat)
    check_plan("fp16/D15 single device", plan)
    for dm in dplan.ops.members:
        if dm.plans is not None:
            check_plan(f"shard member {dm.label}", dm.plans[0])

    x = np.random.default_rng(SEED).standard_normal(s.shape[1]) \
        .astype(np.float32)
    xj = jnp.asarray(x)
    y1, t1 = timed(plan.spmv, mat, xj)
    yd, td = timed(dplan.spmv, x)
    y1 = np.asarray(y1, np.float64)
    yd = np.asarray(yd, np.float64)
    # both are fp32 sums of the same fp16 products in different orders:
    # per row each is within k 2^-24 (|A_q| |x|) of A_q x
    k = int(np.diff(s.indptr).max())
    scale = 1.01 * (abs(s) @ np.abs(x.astype(np.float64)))
    worst = float(np.max(np.abs(yd - y1) / np.maximum(scale, 1e-300)))
    say(f"dist spmv vs single-device plan: max |y_d - y_1| / (|A||x|) = "
        f"{worst:.3e} (bound {2 * (k + 1) * 2.0 ** -24:.3e}); chip smoke "
        f"timing, not a benchmark: first calls {t1:.3f}s single, {td:.3f}s "
        f"dist")
    check(np.isfinite(yd).all() and worst <= 2 * (k + 1) * 2.0 ** -24,
          f"distributed SpMV differs from the single-device plan: {worst:.3e}")

    b = np.random.default_rng(SEED + 1).standard_normal(s.shape[0])
    tol, maxiter = 1e-6, 10000
    (xd, info_d), t_d = timed(lambda: cg.jacobi_pcg_dist(
        dplan, s.diagonal(), jnp.asarray(b), tol=tol, maxiter=maxiter,
        dtype=jnp.float64))
    aq = quantized(s, "fp16", 15)
    rd = relres(aq, xd, b)
    say(f"jacobi_pcg_dist iters={int(info_d.iters)} true relres (scipy fp64) "
        f"vs stored operator={rd:.3e}; chip smoke timing, not a benchmark: "
        f"first solve (compile + run) {t_d:.3f}s")
    iters_1 = jacobi_phase(s, mat, plan, b, tol=tol, maxiter=maxiter)
    check(rd <= tol, f"jacobi_pcg_dist true relres {rd:.3e} > {tol}")
    check(int(info_d.iters) == iters_1,
          f"jacobi_pcg_dist took {int(info_d.iters)} iterations, "
          f"jacobi_pcg_stored {iters_1}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip main path; 4: only the "
                         "distributed path over four chips")
    args = ap.parse_args()
    try:
        import jax

        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: FAIL: cannot import the repro package from "
              f"{os.path.join(ROOT, 'src')}: {e}", file=sys.stderr)
        return 1
    jax.config.update("jax_enable_x64", True)   # fp64 outer Krylov steps
    try:
        cache = use_compile_cache()
        entries = len(os.listdir(cache)) if os.path.isdir(cache) else 0
        say(f"compile cache: {cache} ({entries} entries at start: "
            f"{'warm' if entries else 'cold'})")
        devs = check_device(args.chips)
        if args.chips == 4:
            run_four_chips()
        else:
            run_one_chip()
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    d = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
