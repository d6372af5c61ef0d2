"""CG / PCG / flexible CG (Notay 2000), jit-compatible with residual history.

Convergence criterion matches the paper's eq. (6): ||b - A x||_2 / ||b||_2 <
tol, tracked via the CG recurrence residual (benchmarks re-verify the true
residual afterwards).

Fused solver step (DESIGN.md §10.4): ``pcg`` / ``adaptive_pcg`` accept a
``jit_cache``/``jit_key`` pair that compiles the ENTIRE solve — setup
(initial residual, preconditioned direction, norms), the ``while_loop``
recurrence (matvec + α/β axpys + residual dot in one loop body) and the
epilogue — into one cached jitted, buffer-donating dispatch, so repeated
solves pay zero per-call tracing and zero intermediate host round-trips.
``jacobi_pcg_stored`` parks its fused solve on the plan's function cache
automatically. The computation graph is identical to the uncached path, so
iteration counts (and bits) are unchanged.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro import observe as _obs

Matvec = Callable[[jnp.ndarray], jnp.ndarray]


def _donate(*argnums) -> tuple:
    """Donation argnums, except on CPU where XLA cannot alias the buffers
    and jit would warn on every call."""
    return argnums if jax.default_backend() != "cpu" else ()


class SolveInfo(NamedTuple):
    iters: jnp.ndarray       # iterations executed
    relres: jnp.ndarray      # final relative residual (recurrence)
    history: jnp.ndarray     # relres per iteration, -1 past convergence


class AdaptiveSolveInfo(NamedTuple):
    """Outcome of :func:`adaptive_pcg` (all device scalars/arrays)."""

    iters: jnp.ndarray         # outer (refinement) steps executed
    relres: jnp.ndarray        # final TRUE relative residual ||b-Ax||/||b||
    history: jnp.ndarray       # true relres per outer step, -1 past end
    tier_history: jnp.ndarray  # int32 tier used per outer step, -1 past end
    promotions: jnp.ndarray    # number of codec-tier promotions
    tier_matvecs: jnp.ndarray  # int32[n_tiers] inner matvecs per tier
    hi_matvecs: jnp.ndarray    # high-precision (residual) matvecs


def dist_dot(axis_name: str):
    """⟨a, b⟩ over a device mesh axis: the local partial reduces with a
    ``psum`` so every shard holds the identical global scalar (vectors are
    real; σ/shard padding slots must be zero — the distributed layer's
    row-mask invariant guarantees it for its vectors)."""
    return lambda a, b: _allreduce(jnp.vdot(a, b), axis_name)


def dist_norm(axis_name: str):
    """‖a‖₂ over a device mesh axis (psum of local squared sums)."""
    return lambda a: jnp.sqrt(_allreduce(jnp.sum(a * a), axis_name))


def _prep(b, x0, dtype, norm):
    dtype = dtype or b.dtype
    b = b.astype(dtype)
    x0 = jnp.zeros_like(b) if x0 is None else x0.astype(dtype)
    bnorm = norm(b)
    bnorm = jnp.where(bnorm == 0, 1.0, bnorm)
    return b, x0, bnorm, dtype


def pcg(matvec: Matvec, b: jnp.ndarray, *, M: Matvec | None = None,
        tol: float = 1e-9, maxiter: int = 1000, x0=None,
        dtype=None, dot=None, norm=None, jit_cache: dict | None = None,
        jit_key=None) -> tuple[jnp.ndarray, SolveInfo]:
    """Preconditioned CG. ``M`` must be a *fixed* operator (SPD).

    ``dot`` / ``norm`` default to the single-device ``jnp.vdot`` /
    ``jnp.linalg.norm``; the distributed solvers inject psum-reduced
    versions (:func:`dist_dot` / :func:`dist_norm`) so the identical
    iteration runs on sharded vectors inside a shard_map region — the
    recurrence, and therefore the iteration count, is unchanged.

    ``jit_cache`` (any dict the caller owns, e.g. a plan's ``_fns``)
    compiles the whole solve once per ``(jit_key, tol, maxiter, shape,
    dtype)`` into a single buffer-donating dispatch — the fused solver
    step. The caller must guarantee ``jit_key`` uniquely identifies the
    ``matvec``/``M``/``dot``/``norm`` closures it passes.
    """
    if jit_cache is not None and not isinstance(b, jax.core.Tracer):
        b = jnp.asarray(b)
        sdtype = jnp.dtype(dtype or b.dtype)
        key = ("pcg", jit_key, float(tol), int(maxiter), b.shape,
               sdtype.name)
        fn = jit_cache.get(key)
        if fn is None:
            fn = jax.jit(
                lambda b, x0: pcg(matvec, b, M=M, tol=tol, maxiter=maxiter,
                                  x0=x0, dtype=dtype, dot=dot, norm=norm),
                donate_argnums=_donate(1))
            jit_cache[key] = fn
        # donation must never eat a caller-owned buffer: copy supplied x0
        x0 = (jnp.zeros(b.shape, sdtype) if x0 is None
              else jnp.array(x0, sdtype, copy=True))
        x, info = fn(b, x0)
        _obs.record_solve("pcg", info, path="jit_cache")
        return x, info

    dot = dot or jnp.vdot
    norm = norm or jnp.linalg.norm
    M = M or (lambda r: r)
    # every op but the matvec sits under packsell.solver_vec: the dots,
    # norms, axpys, the preconditioner apply and the history update
    with _obs.span("packsell.solver_vec"):
        b, x0, bnorm, dtype = _prep(b, x0, dtype, norm)
    Ax0 = matvec(x0)
    with _obs.span("packsell.solver_vec"):
        r0 = b - Ax0.astype(dtype)
        z0 = M(r0).astype(dtype)
        rz0 = dot(r0, z0)
        hist0 = jnp.full((maxiter + 1,), -1.0, dtype=jnp.float64 if
                         dtype == jnp.float64 else jnp.float32)
        hist0 = hist0.at[0].set(norm(r0) / bnorm)

    def cond(s):
        k, x, r, z, p, rz, hist, done = s
        return jnp.logical_and(k < maxiter, jnp.logical_not(done))

    def body(s):
        k, x, r, z, p, rz, hist, done = s
        Ap = matvec(p)
        with _obs.span("packsell.solver_vec"):
            Ap = Ap.astype(dtype)
            pAp = dot(p, Ap)
            alpha = rz / jnp.where(pAp == 0, 1.0, pAp)
            x = x + alpha * p
            r = r - alpha * Ap
            relres = norm(r) / bnorm
            hist = hist.at[k + 1].set(relres.astype(hist.dtype))
            done = relres < tol
            z = M(r).astype(dtype)
            rz_new = dot(r, z)
            beta = rz_new / jnp.where(rz == 0, 1.0, rz)
            p = z + beta * p
            return (k + 1, x, r, z, p, rz_new, hist, done)

    s0 = (jnp.asarray(0), x0, r0, z0, z0, rz0, hist0, jnp.asarray(False))
    with _obs.span("packsell.solver_while"):
        k, x, r, z, p, rz, hist, done = jax.lax.while_loop(cond, body, s0)
    with _obs.span("packsell.solver_vec"):
        info = SolveInfo(k, norm(r) / bnorm, hist)
    _obs.record_solve("pcg", info, path="eager")
    return x, info


def fcg(matvec: Matvec, b: jnp.ndarray, *, M: Matvec, tol: float = 1e-9,
        maxiter: int = 1000, x0=None,
        dtype=None) -> tuple[jnp.ndarray, SolveInfo]:
    """Flexible CG (Notay 2000), FCG(1): tolerates a varying preconditioner
    (e.g. an inner Krylov solve — the IO-CG outer iteration, paper §5.2.2)."""
    b, x0, bnorm, dtype = _prep(b, x0, dtype, jnp.linalg.norm)

    r0 = b - matvec(x0).astype(dtype)
    z0 = M(r0).astype(dtype)
    p0 = z0
    hist0 = jnp.full((maxiter + 1,), -1.0, dtype=jnp.float64 if
                     dtype == jnp.float64 else jnp.float32)
    hist0 = hist0.at[0].set(jnp.linalg.norm(r0) / bnorm)

    def cond(s):
        k, x, r, p, hist, done = s
        return jnp.logical_and(k < maxiter, jnp.logical_not(done))

    def body(s):
        k, x, r, p, hist, done = s
        Ap = matvec(p).astype(dtype)
        pAp = jnp.vdot(p, Ap)
        alpha = jnp.vdot(p, r) / jnp.where(pAp == 0, 1.0, pAp)
        x = x + alpha * p
        r = r - alpha * Ap
        relres = jnp.linalg.norm(r) / bnorm
        hist = hist.at[k + 1].set(relres.astype(hist.dtype))
        done = relres < tol
        z = M(r).astype(dtype)
        # one-step A-orthogonalization against the previous direction
        beta = jnp.vdot(z, Ap) / jnp.where(pAp == 0, 1.0, pAp)
        p = z - beta * p
        return (k + 1, x, r, p, hist, done)

    s0 = (jnp.asarray(0), x0, r0, p0, hist0, jnp.asarray(False))
    k, x, r, p, hist, done = jax.lax.while_loop(cond, body, s0)
    return x, SolveInfo(k, jnp.linalg.norm(r) / bnorm, hist)


def jacobi_pcg_stored(mat, plan, diag: jnp.ndarray, b: jnp.ndarray, *,
                      tol: float = 1e-9, maxiter: int = 1000,
                      dtype=None) -> tuple[jnp.ndarray, SolveInfo]:
    """Jacobi-PCG run entirely in σ-stored-row order (plan engine fast path).

    The operator is the symmetrically permuted ``P A Pᵀ`` (SPD iff A is):
    the matvec consumes the stored → original-order gather and the kernel's
    ``permuted=True`` output is already stored-row order — the σ-scatter
    epilogue is skipped on every iteration. The Jacobi preconditioner and
    the right-hand side are permuted ONCE at setup. σ-padding slots stay
    zero throughout, so stored-space dot products and norms equal their
    original-space values and the convergence criterion is unchanged.

    The WHOLE solve — permutation setup, the PCG ``while_loop`` (matvec +
    α/β axpys + residual dot), and the final unpermute — is one jitted,
    buffer-donating dispatch cached on the plan (DESIGN.md §10.4): the
    plan's device operands flow as arguments, repeated solves re-trace
    nothing, and the computation graph (hence the iteration count, bit for
    bit) matches the historical eager path.

    ``mat``/``plan``: a PackSELL matrix and its SpMVPlan (see
    ``OperatorSet.plan_pair``); ``diag``: the matrix diagonal in original
    row order.
    """
    diag = jnp.asarray(diag)
    b = jnp.asarray(b)
    if (plan.ephemeral or plan.inv_cat is None
            or isinstance(b, jax.core.Tracer)):
        # tracing / ephemeral fallback: same graph, no caching
        dinv = jnp.where(diag == 0, 1.0, 1.0 / diag)
        dinv_s = plan.to_stored(dinv.astype(b.dtype))
        b_s = plan.to_stored(b)

        def matvec_s(x_s):
            return plan.spmv(mat, plan.from_stored(x_s), permuted=True)

        x_s, info = pcg(matvec_s, b_s, M=lambda r: r * dinv_s, tol=tol,
                        maxiter=maxiter, dtype=dtype)
        _obs.record_solve("jacobi_pcg_stored", info, path="fallback")
        return plan.from_stored(x_s), info

    fn = stored_solve_fn(plan, b, tol=tol, maxiter=maxiter, dtype=dtype)
    x0_s = jnp.zeros((plan.total_stored,),
                     dtype if dtype is not None else b.dtype)
    with _obs.host_span("packsell.dispatch", kind="pcg"):
        x, info = fn(mat, plan._device_operands(), diag, b, x0_s)
    _obs.record_solve("jacobi_pcg_stored", info, path="fused")
    return x, info


def stored_solve_fn(plan, b, *, tol: float, maxiter: int, dtype=None):
    """The whole :func:`jacobi_pcg_stored` solve for a right-hand side
    shaped like ``b`` (an array or ``jax.ShapeDtypeStruct``) as one jitted
    function ``(mat, dev, diag, b, x0_s) -> (x, SolveInfo)``, cached on the
    plan. ``dev`` is ``plan._device_operands()``; ``x0_s``, the
    stored-order initial guess, is donated."""
    from repro.kernels import plan as _kp

    sdtype = jnp.dtype(dtype if dtype is not None else b.dtype)
    key = ("jpcg_stored", float(tol), int(maxiter), tuple(b.shape),
           sdtype.name)
    fn = plan._fns.get(key)
    if fn is None:
        # the σ-permutes sit under packsell.stored_permute, never inside
        # an SpMV scope or packsell.solver_vec
        def solve(mat_a, dev, diag_a, b_a, x0_s):
            with _obs.span("packsell.stored_permute"):
                dinv = jnp.where(diag_a == 0, 1.0, 1.0 / diag_a)
                dinv_s = _kp.stored_permute(dinv.astype(b_a.dtype),
                                            dev["outrow"], plan.n)
                b_s = _kp.stored_permute(b_a, dev["outrow"], plan.n)

            def matvec_s(x_s):
                with _obs.span("packsell.stored_permute"):
                    x = _kp.stored_unpermute(x_s, dev["inv"])
                return plan.execute_with(mat_a, dev, x, permuted=True)

            x_s, info = pcg(matvec_s, b_s, M=lambda r: r * dinv_s,
                            tol=tol, maxiter=maxiter, dtype=dtype,
                            x0=x0_s)
            with _obs.span("packsell.stored_permute"):
                return _kp.stored_unpermute(x_s, dev["inv"]), info

        fn = jax.jit(solve, donate_argnums=_donate(4))
        plan._fns[key] = fn
    return fn


def _allreduce(v: jnp.ndarray, axis_name: str) -> jnp.ndarray:
    """``psum`` of a local partial under the device scope
    ``packsell.allreduce`` (inside ``pcg`` it nests under
    ``packsell.solver_vec``, as the dots and norms do)."""
    with _obs.span("packsell.allreduce"):
        return jax.lax.psum(v, axis_name)


def jacobi_pcg_dist(dplan, diag: jnp.ndarray, b: jnp.ndarray, *,
                    tol: float = 1e-9, maxiter: int = 1000,
                    dtype=None, mode: str | None = None
                    ) -> tuple[jnp.ndarray, SolveInfo]:
    """Jacobi-PCG over a device mesh: the ENTIRE solve runs inside one
    jitted shard_map region.

    ``dplan`` is a :class:`~repro.distributed.plan.DistSpMVPlan`; each
    iteration's matvec is the per-shard halo-exchange SpMV body (local
    block overlapping the exchange, remote block on the gathered halo; the
    exchange sits under the device scope ``packsell.halo``), and every
    dot/norm is psum-reduced under ``packsell.allreduce`` (:func:`dist_dot`
    / :func:`dist_norm`) so all shards advance through the identical
    scalar recurrence — the iteration count matches the single-device
    solver up to summation-order rounding. Vectors stay sharded for the
    whole solve; only the final x (and the replicated scalars/history)
    come back to the host.

    ``diag``: matrix diagonal in global row order (the Jacobi
    preconditioner); ``b``: global right-hand side; ``mode`` overrides the
    plan's halo-exchange mode.
    """
    from jax.sharding import PartitionSpec as Pspec

    from repro.parallel.sharding import shard_map_unchecked

    b = jnp.asarray(b)
    dtype = dtype or b.dtype
    mode = mode or dplan.exchange
    diag = jnp.asarray(diag)
    dinv = jnp.where(diag == 0, 1.0, 1.0 / diag).astype(dtype)
    ax = dplan.axis_name

    def build():
        dot, norm = dist_dot(ax), dist_norm(ax)

        def body(dev, bs, ds):
            ops = jax.tree.map(lambda leaf: leaf[0], dev)
            b_l, dinv_l = bs[0], ds[0]

            def matvec(v):
                return dplan.ops.shard_body(ops, v, axis_name=ax, mode=mode)

            x_l, info = pcg(matvec, b_l, M=lambda r: r * dinv_l, tol=tol,
                            maxiter=maxiter, dtype=dtype, dot=dot, norm=norm)
            return x_l[None], info.iters, info.relres, info.history

        f = shard_map_unchecked(
            body, dplan.mesh,
            in_specs=(dplan.dev_specs, Pspec(ax), Pspec(ax)),
            out_specs=(Pspec(ax), Pspec(), Pspec(), Pspec()))
        return jax.jit(f)

    fn = dplan.cached_fn(("pcg", tol, maxiter, jnp.dtype(dtype).name, mode),
                         build)
    xs, k, relres, hist = fn(dplan.dev, dplan.shard_vector(b.astype(dtype)),
                             dplan.shard_vector(dinv))
    info = SolveInfo(k, relres, hist)
    _obs.record_solve("jacobi_pcg_dist", info, shards=dplan.n_shards)
    return dplan.unshard_vector(xs), info


def adaptive_pcg(tiers, b: jnp.ndarray, *, M: Matvec | None = None,
                 matvec_hi: Matvec | None = None, tol: float = 1e-9,
                 maxiter: int = 60, m_in: int = 16, x0=None,
                 dtype=None, stag_factor: float = 0.25,
                 start_tier: int = 0, dot=None, norm=None,
                 prestage=None, jit_cache: dict | None = None,
                 jit_key=None
                 ) -> tuple[jnp.ndarray, AdaptiveSolveInfo]:
    """Residual-adaptive mixed-precision PCG (the paper's §6 recipe,
    iterative-refinement style; DESIGN.md §8.5).

    ``tiers`` is an ordered codec ladder of matvec callables, lowest
    precision first and an (effectively) exact operator last — typically
    ``precision.select.build_tier_matvecs`` over a
    :class:`~repro.precision.select.PrecisionPlan`'s
    :func:`~repro.precision.select.tier_ladder`. The solve runs entirely
    inside ONE ``lax.while_loop``:

    * each outer step runs ``m_in`` inner PCG iterations on the correction
      equation ``A_q d = r`` using the CURRENT tier's low-precision
      operator (and the preconditioner ``M``), then updates ``x`` and
      recomputes the TRUE residual with ``matvec_hi`` (default: the last
      tier) — the classic iterative-refinement outer loop, so the final
      accuracy is set by the outer precision, not the codec;
    * **residual stagnation** — the true residual contracting by less than
      ``stag_factor`` over an outer step (the contraction of refinement is
      ≈ ``ε_codec·κ``, so a weak contraction means the tier's quantization
      floor has been hit) — **promotes** the operator to the next codec
      tier mid-solve. Tier choice is a traced ``lax.switch``: no re-trace,
      no loop exit.

    ``dot`` / ``norm`` default to the single-device reductions; the
    distributed solver injects psum-reduced versions (:func:`dist_dot` /
    :func:`dist_norm`) so the identical recurrence — and therefore the
    iteration and promotion schedule — runs on sharded vectors inside a
    shard_map region. ``prestage`` (distributed: the halo gather) maps the
    matvec input to extra operands every tier *and* ``matvec_hi`` receive
    as trailing arguments; it is hoisted out of the tier ``lax.switch`` so
    one collective per matvec serves whichever tier is active.

    ``jit_cache``/``jit_key`` compile the whole refinement loop into one
    cached buffer-donating dispatch, exactly as in :func:`pcg` (the fused
    solver step; the caller's key must identify the tier closures).

    Returns ``(x, AdaptiveSolveInfo)`` with per-tier matvec counts, so
    callers can verify how much of the solve ran sub-32-bit.
    """
    if not tiers:
        raise ValueError("need at least one tier")
    if jit_cache is not None and not isinstance(b, jax.core.Tracer):
        b = jnp.asarray(b)
        sdtype = jnp.dtype(dtype or b.dtype)
        key = ("adaptive", jit_key, float(tol), int(maxiter), int(m_in),
               float(stag_factor), int(start_tier), b.shape, sdtype.name)
        fn = jit_cache.get(key)
        if fn is None:
            fn = jax.jit(
                lambda b, x0: adaptive_pcg(
                    tiers, b, M=M, matvec_hi=matvec_hi, tol=tol,
                    maxiter=maxiter, m_in=m_in, x0=x0, dtype=dtype,
                    stag_factor=stag_factor, start_tier=start_tier,
                    dot=dot, norm=norm, prestage=prestage),
                donate_argnums=_donate(1))
            jit_cache[key] = fn
        # donation must never eat a caller-owned buffer: copy supplied x0
        x0 = (jnp.zeros(b.shape, sdtype) if x0 is None
              else jnp.array(x0, sdtype, copy=True))
        x, info = fn(b, x0)
        _obs.record_solve("adaptive_pcg", info, path="jit_cache")
        return x, info
    n_tiers = len(tiers)
    dot = dot or jnp.vdot
    norm = norm or jnp.linalg.norm
    pre = prestage or (lambda v: ())
    b, x0, bnorm, dtype = _prep(b, x0, dtype, norm)
    M = M or (lambda r: r)
    hi_raw = matvec_hi or tiers[-1]
    branches = [lambda v, *ex, f=f: f(v, *ex).astype(dtype) for f in tiers]

    def mv(tier, v):
        return jax.lax.switch(tier, branches, v, *pre(v))

    def hi(v):
        return hi_raw(v, *pre(v)).astype(dtype)

    def inner_solve(tier, rhs):
        """m_in PCG iterations on A_tier d = rhs from d0 = 0."""
        d = jnp.zeros_like(rhs)
        r = rhs
        z = M(r).astype(dtype)
        p = z
        rz = dot(r, z)

        def body(_, s):
            d, r, z, p, rz = s
            Ap = mv(tier, p)
            pAp = dot(p, Ap)
            alpha = rz / jnp.where(pAp == 0, 1.0, pAp)
            d = d + alpha * p
            r = r - alpha * Ap
            z = M(r).astype(dtype)
            rz_new = dot(r, z)
            beta = rz_new / jnp.where(rz == 0, 1.0, rz)
            p = z + beta * p
            return (d, r, z, p, rz_new)

        d, *_ = jax.lax.fori_loop(0, m_in, body, (d, r, z, p, rz))
        return d

    hist_dtype = jnp.float64 if dtype == jnp.float64 else jnp.float32
    r0 = b - hi(x0).astype(dtype)
    rel0 = norm(r0) / bnorm
    hist0 = jnp.full((maxiter + 1,), -1.0, hist_dtype).at[0].set(
        rel0.astype(hist_dtype))
    thist0 = jnp.full((maxiter + 1,), -1, jnp.int32)
    mv0 = jnp.zeros((n_tiers,), jnp.int32)

    def cond(s):
        k, x, r, relres, tier, nprom, mvc, hic, hist, thist = s
        return jnp.logical_and(k < maxiter, relres >= tol)

    def body(s):
        k, x, r, relres, tier, nprom, mvc, hic, hist, thist = s
        d = inner_solve(tier, r)
        x = x + d
        r = b - hi(x).astype(dtype)
        rel_new = norm(r) / bnorm
        mvc = mvc.at[tier].add(m_in)
        hic = hic + 1
        # stagnation: the tier's quantization floor caps the contraction
        stalled = rel_new > stag_factor * relres
        promote = jnp.logical_and(
            jnp.logical_and(stalled, rel_new >= tol),
            tier < n_tiers - 1)
        tier_next = tier + promote.astype(tier.dtype)
        hist = hist.at[k + 1].set(rel_new.astype(hist_dtype))
        thist = thist.at[k].set(tier.astype(jnp.int32))
        return (k + 1, x, r, rel_new, tier_next,
                nprom + promote.astype(nprom.dtype), mvc, hic, hist, thist)

    s0 = (jnp.asarray(0), x0, r0, rel0,
          jnp.asarray(min(start_tier, n_tiers - 1)), jnp.asarray(0),
          mv0, jnp.asarray(1), hist0, thist0)
    with _obs.span("packsell.solver_while"):
        k, x, r, relres, tier, nprom, mvc, hic, hist, thist = \
            jax.lax.while_loop(cond, body, s0)
    info = AdaptiveSolveInfo(k, relres, hist, thist, nprom, mvc, hic)
    _obs.record_solve("adaptive_pcg", info, path="eager")
    return x, info


def adaptive_pcg_dist(ladder, diag: jnp.ndarray, b: jnp.ndarray, *,
                      tol: float = 1e-9, maxiter: int = 60, m_in: int = 16,
                      stag_factor: float = 0.25, start_tier: int = 0,
                      dtype=None, mode: str | None = None
                      ) -> tuple[jnp.ndarray, AdaptiveSolveInfo]:
    """Residual-adaptive mixed-precision PCG over a device mesh: the
    ENTIRE tier-promoting refinement loop runs inside ONE jitted shard_map
    region (DESIGN.md §9.4).

    ``ladder`` is a :class:`~repro.distributed.plan.DistTierLadder` — one
    stacked member set per codec tier over one shared partition, plus the
    exact fp64 set for the outer true-residual step. The body is
    :func:`adaptive_pcg` verbatim with three injections:

    * ``dot`` / ``norm`` psum-reduce over the mesh axis, so every shard
      advances through the identical scalar recurrence — iteration counts
      and tier promotions match the single-device solver up to
      summation-order rounding;
    * each tier's matvec is the per-shard composite body
      (``DistOperands.shard_body``) selected by the traced ``lax.switch``;
    * the halo gather is the shared ``prestage``, hoisted out of the
      switch — one collective per matvec regardless of the active tier.

    ``diag``: matrix diagonal in global row order (Jacobi preconditioner);
    ``b``: global right-hand side; ``mode`` overrides the ladder's
    halo-exchange mode.
    """
    from jax.sharding import PartitionSpec as Pspec

    from repro.distributed import halo as dh
    from repro.parallel.sharding import shard_map_unchecked

    b = jnp.asarray(b)
    dtype = dtype or b.dtype
    mode = mode or ladder.exchange
    diag = jnp.asarray(diag)
    dinv = jnp.where(diag == 0, 1.0, 1.0 / diag).astype(dtype)
    ax = ladder.axis_name
    h_pad = ladder.h_pad

    def build():
        dot, norm = dist_dot(ax), dist_norm(ax)

        def body(dev, bs, ds):
            sh = jax.tree.map(lambda leaf: leaf[0], dev)
            b_l, dinv_l = bs[0], ds[0]
            pre = dh.prestage(sh["shared"], axis_name=ax,
                              n_shards=ladder.n_shards, h_pad=h_pad,
                              mode=mode)

            def tier_fn(ops_t, dev_t):
                def matvec(v, *extras):
                    return ops_t.shard_body(
                        dev_t, v, axis_name=ax, mode=mode,
                        x_halo=extras[0] if extras else None,
                        shared=sh["shared"])
                return matvec

            tiers = [tier_fn(o, d)
                     for o, d in zip(ladder.tiers, sh["tiers"])]
            hi = tier_fn(ladder.hi, sh["hi"])
            x_l, info = adaptive_pcg(
                tiers, b_l, M=lambda r: r * dinv_l, matvec_hi=hi,
                tol=tol, maxiter=maxiter, m_in=m_in, dtype=dtype,
                stag_factor=stag_factor, start_tier=start_tier,
                dot=dot, norm=norm, prestage=pre)
            return (x_l[None],) + tuple(info)

        f = shard_map_unchecked(
            body, ladder.mesh,
            in_specs=(ladder.dev_specs, Pspec(ax), Pspec(ax)),
            out_specs=(Pspec(ax),) + (Pspec(),) * 7)
        return jax.jit(f)

    fn = ladder.cached_fn(
        ("adaptive", tol, maxiter, m_in, stag_factor, start_tier,
         jnp.dtype(dtype).name, mode), build)
    out = fn(ladder.dev, ladder.shard_vector(b.astype(dtype)),
             ladder.shard_vector(dinv))
    info = AdaptiveSolveInfo(*out[1:])
    _obs.record_solve("adaptive_pcg_dist", info, shards=ladder.n_shards)
    return ladder.unshard_vector(out[0]), info


def pcg_fixed_iters(matvec: Matvec, M: Matvec, m_in: int,
                    dtype=jnp.float32) -> Matvec:
    """m_in PCG iterations from x0 = 0, packaged as a preconditioner —
    the inner solver of IO-CG (paper §5.2.2)."""

    def apply(rhs: jnp.ndarray) -> jnp.ndarray:
        b = rhs.astype(dtype)
        x = jnp.zeros_like(b)
        r = b
        z = M(r).astype(dtype)
        p = z
        rz = jnp.vdot(r, z)

        def body(_, s):
            x, r, z, p, rz = s
            Ap = matvec(p).astype(dtype)
            pAp = jnp.vdot(p, Ap)
            alpha = rz / jnp.where(pAp == 0, 1.0, pAp)
            x = x + alpha * p
            r = r - alpha * Ap
            z = M(r).astype(dtype)
            rz_new = jnp.vdot(r, z)
            beta = rz_new / jnp.where(rz == 0, 1.0, rz)
            p = z + beta * p
            return (x, r, z, p, rz_new)

        x, *_ = jax.lax.fori_loop(0, m_in, body, (x, r, z, p, rz))
        return x

    return apply
