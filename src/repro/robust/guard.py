"""Integrity validation + the ABFT checksum guard (DESIGN.md §11).

Two complementary detection layers, priced separately:

* **Structural validation** (:func:`validate_matrix`, :func:`validate_plan`,
  :func:`validate_composite`) — host-side numpy passes over the packed /
  fused operands: checkpoint monotonicity and range, fused-stream length
  accounting, delta-field (column) range, permutation bijectivity. Run at
  build time (the cheap subset, see ``kernels.plan._quick_validate``) and
  on demand after suspicion.

* **The ABFT guard** (:func:`build_guard` + :func:`guarded_spmv`) — the
  classic algorithm-based-fault-tolerance checksum-vector construction:
  precompute ``c = eᵀA`` (fp64 column sums of the *decoded* operator) once
  at build, then every guarded matvec verifies ``c·x ≈ sum(y)`` in fp64
  inside the SAME jitted dispatch — one extra dot per matvec — with a
  codec-aware tolerance derived from the PR-3 error model
  (``precision.analyze.ulp_bound``). The analytic identity is blind to
  corruptions below the fp32 rounding floor (a low-order mantissa flip in
  one packed fp16 payload moves ``sum(y)`` by ~2⁻¹¹·|a·x_j|, far under any
  honest tolerance over thousands of nonzeros), so the same dispatch also
  recomputes an exact mod-2³² word checksum over every operand array the
  execution reads: a single flipped bit changes the sum by ±2^b ≠ 0
  (mod 2³²), so single-word operand corruption is detected exactly, at the
  cost of one integer pass over the stream.

What each layer catches: the checksum — any operand corruption (words,
checkpoints, cursor caches, permutations), including value-neutral ones;
the analytic identity — NaN/Inf poison in ``x`` or the operands, and any
corruption introduced *before* the guard was built when the reference
column sums come from the original CSR (``build_guard(..., csr=...)``).
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codecs as cd
from repro.core.packsell import PackSELLMatrix
from repro.observe import metrics as _obs


class IntegrityError(ValueError):
    """An operand failed structural validation or a guard check."""


# ---------------------------------------------------------------------------
# Plan health (serving integration: tripped plans are rebuilt before reuse)
# ---------------------------------------------------------------------------


def mark_unhealthy(plan, reason: str) -> None:
    """Flag a plan as tripped; the serving engine rebuilds flagged plans
    before reuse (``serving.engine.DecodeEngine.warmup``)."""
    plan._unhealthy = str(reason)
    _obs.inc("guard.plan_unhealthy", reason=str(reason))


def plan_health(plan) -> str | None:
    """The trip reason, or None for a healthy plan."""
    return getattr(plan, "_unhealthy", None)


def is_healthy(plan) -> bool:
    return plan_health(plan) is None


# ---------------------------------------------------------------------------
# Exact mod-2^32 operand checksums
# ---------------------------------------------------------------------------


def _as_u32_np(a: np.ndarray) -> np.ndarray:
    a = np.ascontiguousarray(a)
    if a.dtype == np.uint32:
        return a
    if a.dtype.itemsize in (4, 8):
        return a.view(np.uint32)    # 64-bit: both halves, matching bitcast
    return a.astype(np.uint32)


def checksum(arrays) -> np.uint64:
    """Host reference checksum over every 32-bit word in ``arrays``: the
    mod-2³² word sum (any single-bit flip changes it by ±2^b ≠ 0 — exact
    single-word detection) packed with a position-weighted sum
    ``Σ (i+1)·wᵢ mod 2³²`` (a plain sum is blind to *transpositions* —
    e.g. a swapped permutation pair — the weighted sum is not, unless
    ``(wᵢ−wⱼ)·(j−i) ≡ 0 mod 2³²``, which a random swap essentially never
    hits). Matches the device-side :func:`_checksum_jnp` bit for bit."""
    s0 = 0
    s1 = 0
    for a in arrays:
        a = np.asarray(a)
        if not a.size:
            continue
        u = _as_u32_np(a).ravel()
        s0 = (s0 + int(u.sum(dtype=np.uint32))) & 0xFFFFFFFF
        w = np.arange(1, u.size + 1, dtype=np.uint32)
        s1 = (s1 + int((u * w).sum(dtype=np.uint32))) & 0xFFFFFFFF
    return np.uint64((s0 << 32) | s1)


def _checksum_ref_pair(ref: np.uint64):
    ref = np.uint64(ref)
    return (np.uint32(ref >> np.uint64(32)),
            np.uint32(ref & np.uint64(0xFFFFFFFF)))


def _checksum_jnp(arrays):
    """Device-side (plain, weighted) mod-2³² word checksums (same values
    as the two halves of :func:`checksum`)."""
    s0 = jnp.uint32(0)
    s1 = jnp.uint32(0)
    for a in arrays:
        if a is None or a.size == 0:
            continue
        if a.dtype.itemsize < 4:
            a = a.astype(jnp.uint32)        # widened, as _as_u32_np does
        elif a.dtype != jnp.uint32:
            a = jax.lax.bitcast_convert_type(a, jnp.uint32)
        u = a.ravel()
        s0 = s0 + jnp.sum(u, dtype=jnp.uint32)
        w = jnp.arange(1, u.size + 1, dtype=jnp.uint32)
        s1 = s1 + jnp.sum(u * w, dtype=jnp.uint32)
    return s0, s1


def guard_arrays(mat: PackSELLMatrix, plan) -> list:
    """Every operand array the plan's execution path actually reads — the
    checksum coverage set (and the injection surface of
    ``robust.inject``). Fused 'jnp' plans stream the repacked words, not
    the bucketed packs, so only the former is covered; a plan with a
    windowed x gather adds its window operands."""
    dev = plan._device_operands()
    arrs = []
    if dev.get("fused") is not None and plan.variant == "jnp":
        arrs += [dev["fused"][0], dev["fused"][1]]
    else:
        arrs += list(mat.packs) + list(mat.d0s)
        if dev.get("cols") is not None:
            arrs += list(dev["cols"])
        if dev.get("kckpt") is not None:
            arrs += list(dev["kckpt"])
    arrs += jax.tree.leaves(dev.get("window"))
    if dev.get("inv2") is not None:
        arrs.append(dev["inv2"])
    elif dev.get("inv") is not None:
        arrs.append(dev["inv"])
    arrs.append(dev["outrow"])
    return arrs


# ---------------------------------------------------------------------------
# ABFT column sums (host, fp64)
# ---------------------------------------------------------------------------


def matrix_colsums(mat: PackSELLMatrix):
    """``(c, cabs)``: fp64 column sums of the decoded (quantized) operator
    and of its magnitudes — the ABFT checksum vectors. Decoding the packed
    words (not the source CSR) makes ``c·x = eᵀ(Ax)`` exact up to fp32
    matvec rounding: quantization cancels out of the identity."""
    c = np.zeros(mat.m, np.float64)
    cabs = np.zeros(mat.m, np.float64)
    codec = mat.codec
    for pack, d0, outrow in zip(mat.packs, mat.d0s, mat.outrows):
        words = np.asarray(pack)
        S, w, C = words.shape
        if words.size == 0:
            continue
        v, d, flag = cd.unpack_words_np(words.reshape(-1), codec, mat.D)
        v = v.astype(np.float64).reshape(S, w, C)
        cols = np.asarray(d0)[:, None, None].astype(np.int64) + \
            np.cumsum(d.astype(np.int64).reshape(S, w, C), axis=1)
        rows_ok = (np.asarray(outrow).reshape(S, C) < mat.n)[:, None, :]
        valid = (flag.reshape(S, w, C) == 1) & rows_ok
        cc = np.clip(cols[valid], 0, max(mat.m - 1, 0))
        np.add.at(c, cc, v[valid])
        np.add.at(cabs, cc, np.abs(v[valid]))
    return c, cabs


def _max_row_words(mat: PackSELLMatrix) -> int:
    return max((int(p.shape[1]) for p in mat.packs), default=1)


@dataclasses.dataclass
class GuardState:
    """Per-plan ABFT guard operands, built once (:func:`build_guard`).

    ``tau_rel`` scales the fp32 rounding-noise bound: the guarded matvec
    accepts ``|sum(y) - c·x| <= tau_rel·(cabs·|x| + |c·x|) + tau_quant·
    cabs·|x|``. ``tau_quant`` is nonzero only when the column sums come
    from the original CSR (``source='csr'``): the decoded operator then
    differs from the reference by codec quantization, bounded per entry by
    the PR-3 error model's ``ulp_bound(codec, D)``.

    ``every`` amortizes the full guard: with ``every=K > 1``, only every
    K-th :func:`guarded_spmv` call runs the ABFT identity + exact operand
    checksum; the other K-1 calls run a single-reduction finiteness check
    on ``y`` (which still catches NaN/Inf poisoning immediately). Exact
    reductions on this backend cost ~0.2 ns/word — comparable to the SpMV
    itself on very sparse matrices — so the full guard cannot be free per
    call; striding bounds silent-corruption detection latency at K
    matvecs while keeping steady-state overhead ~(full_cost/K).
    """

    c: jnp.ndarray            # fp64 [m] colsums
    cabs: jnp.ndarray         # fp64 [m] magnitude colsums
    ref_checksum: np.uint64   # packed (plain, weighted) operand checksum
    tau_rel: float
    tau_quant: float
    source: str               # 'decoded' | 'csr'
    every: int = 1            # full-guard stride (1 = every call)
    calls: int = 0            # guarded_spmv call counter (host-side)
    calls_since_full: int = 0  # light checks since the last full guard
    last_check_latency: int = 1  # detection-latency (calls) of the most
    #                              recent check: guarded calls since the
    #                              last full guard, inclusive — the window
    #                              a silent corruption could have survived
    _dev: dict | None = dataclasses.field(default=None, repr=False)

    def dev(self) -> dict:
        """The jit-argument form (so one compiled guard serves rebuilt
        guard states). Cached — rebuilding these small device arrays per
        call would dominate the guard's cost on small matrices."""
        if self._dev is None:
            self._dev = {
                "c": self.c, "cabs": self.cabs,
                "ref": jnp.asarray(_checksum_ref_pair(self.ref_checksum),
                                   jnp.uint32),
                "tau": jnp.asarray([self.tau_rel, self.tau_quant],
                                   jnp.float64)}
        return self._dev

    def refresh_checksum(self, mat: PackSELLMatrix, plan) -> None:
        """Re-baseline the operand checksum (after a legitimate operand
        change, e.g. ``plan.retile``)."""
        self.ref_checksum = checksum(guard_arrays(mat, plan))
        self._dev = None


#: guard-tolerance safety factor over the worst-case fp32 rounding model
#: (per-term rounding ~eps32, row depth w, plus gather/fma reassociation
#: slack). Loose enough that clean solves never trip; the exact checksum,
#: not this tolerance, carries the single-bit detection guarantee.
_TAU_SAFETY = 32.0


def build_guard(mat: PackSELLMatrix, plan, *, csr=None,
                safety: float = _TAU_SAFETY,
                every: int | None = None) -> GuardState:
    """Precompute the ABFT guard for ``(mat, plan)``: fp64 column sums
    (``c = eᵀA``), the exact operand word checksum, and the tolerance
    constants. ``csr`` (the original scipy matrix) switches the reference
    column sums to the *source* data — the guard then also certifies the
    packing itself, at the price of a codec-aware quantization term
    (``precision.analyze.ulp_bound``) in the tolerance. ``every`` is the
    full-guard stride (default: env ``REPRO_GUARD_EVERY``, else 1 —
    fully guarded)."""
    from repro.precision import analyze as an

    if every is None:
        every = int(os.environ.get("REPRO_GUARD_EVERY", "1"))
    if every < 1:
        raise ValueError(f"build_guard: every must be >= 1, got {every}")

    if csr is not None:
        a = csr.tocsr().astype(np.float64)
        c = np.asarray(a.sum(axis=0)).ravel()
        cabs = np.asarray(abs(a).sum(axis=0)).ravel()
        tau_quant = float(an.ulp_bound(mat.codec_name, mat.D))
        if not np.isfinite(tau_quant):
            raise IntegrityError(
                f"codec {mat.codec_name!r} has no finite ulp bound; build "
                f"the guard from the decoded operator (csr=None)")
        source = "csr"
    else:
        c, cabs = matrix_colsums(mat)
        tau_quant = 0.0
        source = "decoded"
    eps32 = float(np.finfo(np.float32).eps)
    tau_rel = safety * eps32 * (_max_row_words(mat) + 8)
    return GuardState(
        c=jnp.asarray(c, jnp.float64), cabs=jnp.asarray(cabs, jnp.float64),
        ref_checksum=checksum(guard_arrays(mat, plan)),
        tau_rel=tau_rel, tau_quant=tau_quant, source=source, every=every)


def _guard_terms(gdev: dict, x, y):
    """The shared guard arithmetic: fp64 ABFT sums + finite checks.
    Returns (ok_analytic, rel_err)."""
    x64 = x.astype(jnp.float64)
    s_y = jnp.sum(y.astype(jnp.float64))
    s_c = jnp.dot(gdev["c"], x64)
    mag = jnp.dot(gdev["cabs"], jnp.abs(x64))
    tau = gdev["tau"][0] * (mag + jnp.abs(s_c)) + gdev["tau"][1] * mag
    err = jnp.abs(s_y - s_c)
    # NaN/Inf anywhere => comparisons go False / err non-finite: tripped
    ok = (err <= tau) & jnp.all(jnp.isfinite(y)) & jnp.isfinite(mag)
    rel = err / jnp.where(mag > 0, mag, 1.0)
    return ok, rel


def guarded_spmv(mat: PackSELLMatrix, plan, gs: GuardState, x, *,
                 full: bool | None = None):
    """``(y, ok, rel_err)`` — the guarded matvec: the plan's normal
    execution body plus the ABFT identity check and the exact operand
    checksum, all inside ONE jitted dispatch. ``ok`` is a device bool
    scalar (False = guard tripped); ``rel_err`` the analytic residual
    scaled by ``cabs·|x|``. Callers that confirm a trip should
    :func:`mark_unhealthy` the plan.

    ``full`` selects the check depth: ``True`` = ABFT identity + exact
    operand checksum, ``False`` = one-reduction finiteness check on ``y``
    only (``rel_err`` is then 0). ``None`` (default) follows the guard's
    amortization stride: full every ``gs.every``-th call, light
    otherwise — see :class:`GuardState`."""
    if not isinstance(x, jax.Array):   # asarray on a device array is ~30us
        x = jnp.asarray(x)
    if full is None:
        full = gs.every <= 1 or (gs.calls % gs.every == 0)
        gs.calls += 1
    traced = plan.ephemeral or isinstance(x, jax.core.Tracer)
    if not traced:
        # detection-latency accounting (host entry points only; a traced
        # guard is one fused check inside the caller's loop)
        gs.last_check_latency = gs.calls_since_full + 1
        gs.calls_since_full = 0 if full else gs.calls_since_full + 1
        _obs.inc("guard.check", depth="full" if full else "light")
    if not full and not traced:
        key = ("guarded_spmv_light", x.shape, x.dtype)
        fn = plan._fns.get(key)
        if fn is None:
            def impl_light(matv, dev, xx):
                y = plan._execute(matv, dev, xx, False)
                # isfinite fuses into the boolean reduce (no materialized
                # temporary) — the cheapest real check on this backend
                return (y, jnp.all(jnp.isfinite(y)),
                        jnp.zeros((), jnp.float64))

            fn = jax.jit(impl_light)
            plan._fns[key] = fn
        return fn(plan._exec_mat(mat), plan._device_operands(), x)
    if plan.ephemeral or isinstance(x, jax.core.Tracer):
        dev = plan._device_operands()
        y = plan._execute(mat, dev, x, False)
        gdev = gs.dev()
        ok, rel = _guard_terms(gdev, x, y)
        with _obs.span("packsell.guard_checksum"):
            cs0, cs1 = _checksum_jnp(guard_arrays(mat, plan))
        return (y, ok & (cs0 == gdev["ref"][0]) & (cs1 == gdev["ref"][1]),
                rel)

    key = ("guarded_spmv", x.shape, x.dtype)
    fn = plan._fns.get(key)
    if fn is None:
        def impl(matv, dev, gdev, xx):
            y = plan._execute(matv, dev, xx, False)
            ok, rel = _guard_terms(gdev, xx, y)
            with _obs.span("packsell.guard_checksum"):
                cs0, cs1 = _checksum_jnp(_guard_arrays_traced(matv, dev,
                                                              plan))
            return (y, ok & (cs0 == gdev["ref"][0])
                    & (cs1 == gdev["ref"][1]), rel)

        fn = jax.jit(impl)
        plan._fns[key] = fn
    # the fused 'jnp' variant streams the plan operands, not mat.packs --
    # but the checksum of the non-fused variants covers the packs, so only
    # ship the placeholder view when the packs are NOT read
    matv = plan._exec_mat(mat)
    return fn(matv, plan._device_operands(), gs.dev(), x)


def _guard_arrays_traced(matv, dev, plan):
    """The :func:`guard_arrays` coverage set from jit-argument operands
    (shared by the spmv and spmm guarded bodies)."""
    arrs = []
    if dev.get("fused") is not None and plan.variant == "jnp":
        arrs += [dev["fused"][0], dev["fused"][1]]
    else:
        arrs += list(matv.packs) + list(matv.d0s)
        if dev.get("cols") is not None:
            arrs += list(dev["cols"])
        if dev.get("kckpt") is not None:
            arrs += list(dev["kckpt"])
    arrs += jax.tree.leaves(dev.get("window"))
    if dev.get("inv2") is not None:
        arrs.append(dev["inv2"])
    elif dev.get("inv") is not None:
        arrs.append(dev["inv"])
    arrs.append(dev["outrow"])
    return arrs


def _guard_terms_mm(gdev: dict, x, y):
    """Per-column ABFT identity for multi-RHS: ``eᵀ(AX) = (eᵀA)X``
    column by column.  Returns (ok over all columns, max column rel)."""
    x64 = x.astype(jnp.float64)
    s_y = jnp.sum(y.astype(jnp.float64), axis=0)          # [nb]
    s_c = gdev["c"] @ x64                                  # [nb]
    mag = gdev["cabs"] @ jnp.abs(x64)                      # [nb]
    tau = gdev["tau"][0] * (mag + jnp.abs(s_c)) + gdev["tau"][1] * mag
    err = jnp.abs(s_y - s_c)
    ok = jnp.all(err <= tau) & jnp.all(jnp.isfinite(y)) \
        & jnp.all(jnp.isfinite(mag))
    rel = jnp.max(err / jnp.where(mag > 0, mag, 1.0))
    return ok, rel


def guarded_spmm(mat: PackSELLMatrix, plan, gs: GuardState, x, *,
                 full: bool | None = None):
    """``(Y, ok, rel_err)`` — the multi-RHS analogue of
    :func:`guarded_spmv` for the serving front end's coalesced slots:
    ``plan.spmm``'s execution body plus a per-column ABFT identity and
    the exact operand checksum in ONE jitted dispatch.  The checksum is
    shared across all ``nb`` columns, so the guard amortizes over the
    batch — guarding a full slot costs the same integer pass as
    guarding one request.  ``full`` semantics match
    :func:`guarded_spmv` (a batch counts as ONE guarded call in the
    stride accounting)."""
    if not isinstance(x, jax.Array):
        x = jnp.asarray(x)
    if x.ndim != 2:
        raise ValueError(f"guarded_spmm wants x of shape [m, nb], got "
                         f"{x.shape}")
    if full is None:
        full = gs.every <= 1 or (gs.calls % gs.every == 0)
        gs.calls += 1
    traced = plan.ephemeral or isinstance(x, jax.core.Tracer)
    if not traced:
        gs.last_check_latency = gs.calls_since_full + 1
        gs.calls_since_full = 0 if full else gs.calls_since_full + 1
        _obs.inc("guard.check", depth="full" if full else "light",
                 op="spmm")
    if traced:
        dev = plan._device_operands()
        y = plan._execute_mm(mat, dev, x, False)
        gdev = gs.dev()
        ok, rel = _guard_terms_mm(gdev, x, y)
        if not full:
            return y, jnp.all(jnp.isfinite(y)), jnp.zeros((), jnp.float64)
        cs0, cs1 = _checksum_jnp(guard_arrays(mat, plan))
        return (y, ok & (cs0 == gdev["ref"][0]) & (cs1 == gdev["ref"][1]),
                rel)
    if not full:
        key = ("guarded_spmm_light", x.shape, x.dtype)
        fn = plan._fns.get(key)
        if fn is None:
            def impl_light(matv, dev, xx):
                y = plan._execute_mm(matv, dev, xx, False)
                return (y, jnp.all(jnp.isfinite(y)),
                        jnp.zeros((), jnp.float64))

            fn = jax.jit(impl_light)
            plan._fns[key] = fn
        return fn(plan._exec_mat(mat), plan._device_operands(), x)

    key = ("guarded_spmm", x.shape, x.dtype)
    fn = plan._fns.get(key)
    if fn is None:
        def impl(matv, dev, gdev, xx):
            y = plan._execute_mm(matv, dev, xx, False)
            ok, rel = _guard_terms_mm(gdev, xx, y)
            with _obs.span("packsell.guard_checksum"):
                cs0, cs1 = _checksum_jnp(_guard_arrays_traced(matv, dev,
                                                              plan))
            return (y, ok & (cs0 == gdev["ref"][0])
                    & (cs1 == gdev["ref"][1]), rel)

        fn = jax.jit(impl)
        plan._fns[key] = fn
    return fn(plan._exec_mat(mat), plan._device_operands(), gs.dev(), x)


def check_integrity(mat: PackSELLMatrix, plan, gs: GuardState) -> bool:
    """Recompute the operand checksum and compare with the build-time
    reference (no matvec) — the cheap on-demand probe ``guarded_solve``
    runs per outer step."""
    cs = checksum([np.asarray(a) for a in guard_arrays(mat, plan)])
    return bool(np.uint64(cs) == np.uint64(gs.ref_checksum))


# ---------------------------------------------------------------------------
# Structural validation
# ---------------------------------------------------------------------------


def validate_matrix(mat: PackSELLMatrix, *, raise_: bool = False) -> list:
    """Structural checks on the packed buckets (host numpy): delta-decoded
    column range, permutation bijectivity, slice-base (d0) range. Returns
    a list of problem strings (empty = valid); ``raise_=True`` raises
    :class:`IntegrityError` instead."""
    issues = []
    codec = mat.codec
    mlim = max(mat.m - 1, 0)
    outrow_all = []
    for b, (pack, d0, outrow) in enumerate(
            zip(mat.packs, mat.d0s, mat.outrows)):
        words = np.asarray(pack)
        d0 = np.asarray(d0)
        outrow = np.asarray(outrow)
        outrow_all.append(outrow)
        S, w, C = words.shape
        if len(d0) != S:
            issues.append(f"bucket {b}: d0 length {len(d0)} != S={S}")
            continue
        if len(outrow) != S * C:
            issues.append(
                f"bucket {b}: outrow length {len(outrow)} != S*C={S * C}")
            continue
        if S and (d0.min(initial=0) < 0 or d0.max(initial=0) > mlim):
            issues.append(f"bucket {b}: d0 outside [0, {mlim}]")
        if words.size == 0:
            continue
        v, d, flag = cd.unpack_words_np(words.reshape(-1), codec, mat.D)
        if not np.all(np.isfinite(v[flag == 1])):
            issues.append(f"bucket {b}: non-finite packed value")
        cols = d0[:, None, None].astype(np.int64) + \
            np.cumsum(d.astype(np.int64).reshape(S, w, C), axis=1)
        rows_ok = (outrow.reshape(S, C) < mat.n)[:, None, :]
        f1 = (flag.reshape(S, w, C) == 1) & rows_ok
        if np.any(f1) and int(cols[f1].max()) > mlim:
            issues.append(
                f"bucket {b}: decoded column {int(cols[f1].max())} >= "
                f"m={mat.m}")
    if outrow_all:
        cat = np.concatenate(outrow_all)
        counts = np.bincount(cat[cat < mat.n], minlength=mat.n)
        if len(cat) and (counts.min(initial=1) < 1
                         or counts.max(initial=1) > 1):
            issues.append("outrow is not a bijection onto [0, n)")
    if issues and raise_:
        raise IntegrityError("; ".join(issues))
    return issues


def validate_plan(mat: PackSELLMatrix, plan, *, raise_: bool = False) -> list:
    """Structural checks on a plan's derived operands: fused-stream length
    accounting, segment coverage, checkpoint monotonicity and range,
    offset (delta-field) range under the stream encoding, inverse-
    permutation bijectivity. Host numpy; run on demand (the cheap subset
    already ran at build — ``kernels.plan._quick_validate``)."""
    issues = []
    outrow = np.asarray(plan.outrow_cat)
    if len(outrow) != plan.total_stored:
        issues.append(f"outrow_cat length {len(outrow)} != total_stored="
                      f"{plan.total_stored}")
    counts = np.bincount(outrow[outrow < plan.n], minlength=plan.n)
    if plan.n and (counts.min(initial=1) < 1 or counts.max(initial=1) > 1):
        issues.append("outrow_cat is not a bijection onto [0, n)")
    if plan.inv_cat is not None:
        inv = np.asarray(plan.inv_cat)
        if len(inv) != plan.n:
            issues.append(f"inv_cat length {len(inv)} != n={plan.n}")
        elif plan.n and not np.array_equal(
                outrow[np.clip(inv, 0, len(outrow) - 1)],
                np.arange(plan.n)):
            issues.append("inv_cat does not invert outrow_cat")
    if plan.inv2_cat is not None and plan.inv_cat is not None:
        inv2 = np.asarray(plan.inv2_cat)
        if not np.array_equal(inv2[:, 0] * mat.C + inv2[:, 1],
                              np.asarray(plan.inv_cat)):
            issues.append("inv2_cat disagrees with inv_cat")

    layout = plan.fused_layout
    if plan.fused is not None and layout is not None:
        words3d = np.asarray(plan.fused[0])
        ckpt = np.asarray(plan.fused[1])
        if words3d.shape != (layout.groups, layout.wr, layout.C):
            issues.append(
                f"fused stream shape {words3d.shape} != layout "
                f"({layout.groups}, {layout.wr}, {layout.C})")
        if ckpt.shape != (layout.groups, layout.C):
            issues.append(f"fused checkpoint shape {ckpt.shape} != "
                          f"({layout.groups}, {layout.C})")
        g_sum = sum(seg.groups for seg in layout.segments)
        if g_sum != layout.groups:
            issues.append(f"segment group accounting {g_sum} != "
                          f"{layout.groups}")
        stored = sum(seg.stored for seg in layout.segments)
        if stored != plan.total_stored:
            issues.append(f"segment stored accounting {stored} != "
                          f"{plan.total_stored}")
        mlim = max(plan.m - 1, 0)
        if ckpt.size and (int(ckpt.min()) < 0 or int(ckpt.max()) > mlim):
            issues.append(f"checkpoint outside [0, {mlim}]")
        for si, seg in enumerate(layout.segments):
            levels = seg.levels
            if any(levels[k] < levels[k + 1]
                   for k in range(len(levels) - 1)):
                issues.append(f"segment {si}: level sizes not "
                              f"non-increasing: {levels}")
            if levels and levels[0] > seg.S:
                issues.append(f"segment {si}: level 0 covers {levels[0]} "
                              f"> S={seg.S} slices")
            # checkpoint monotonicity: along one slice's run chain the
            # cursor may only advance
            if not issues and words3d.size:
                off = 0
                prev = None
                for Sk in levels:
                    cur = ckpt[seg.g0 + off:seg.g0 + off + Sk]
                    if prev is not None and np.any(cur < prev[:Sk]):
                        issues.append(
                            f"segment {si}: checkpoint not monotone")
                        break
                    prev = cur
                    off += Sk
        # offset (delta-field) range under the encoding: every decoded
        # column must land in [0, m)
        if not issues and words3d.size:
            v, local = _decode_stream_np(words3d, mat, layout)
            cols = ckpt[:, None, :].astype(np.int64) + local
            contrib = v != 0
            if np.any(contrib) and int(cols[contrib].max()) > mlim:
                issues.append(
                    f"fused offset overflow: column "
                    f"{int(cols[contrib].max())} >= m={plan.m}")
            if not np.all(np.isfinite(v)):
                issues.append("fused stream decodes a non-finite value")
    if issues and raise_:
        raise IntegrityError("; ".join(issues))
    return issues


def _decode_stream_np(words3d: np.ndarray, mat: PackSELLMatrix, layout):
    """Numpy mirror of ``kernels.plan._fused_decode``: (value fp64,
    run-local offset int64) for the whole stream."""
    w = words3d.astype(np.uint32)
    enc = layout.encoding
    if enc == "f16":
        v = (w >> np.uint32(16)).astype(np.uint16).view(np.float16)
        local = (w & np.uint32(0xFFFF)).astype(np.int64)
    elif enc == "top16":
        v = (w & np.uint32(0xFFFF0000)).view(np.float32)
        local = (w & np.uint32(0xFFFF)).astype(np.int64)
    elif enc == "fixed16":
        v = (w.view(np.int32) >> np.int32(16)).astype(np.float64) \
            * layout.scale
        local = (w & np.uint32(0xFFFF)).astype(np.int64)
    else:                            # 'words'
        v, d, flag = cd.unpack_words_np(w.reshape(-1), mat.codec, mat.D)
        v = np.where(flag == 1, v, 0.0).reshape(w.shape)
        local = d.astype(np.int64).reshape(w.shape)
    return np.asarray(v, np.float64), local


def validate_composite(comp, *, raise_: bool = False) -> list:
    """Validate every member block of a
    :class:`~repro.kernels.composite.CompositePlan` plus the per-term
    inverse permutations (each term's inverse must index a valid slot per
    covered row)."""
    issues = []
    for i, mem in enumerate(comp.members):
        if isinstance(mem.mat, PackSELLMatrix):
            for msg in validate_matrix(mem.mat):
                issues.append(f"member {i} ({mem.label}): {msg}")
            if mem.plan is not None:
                for msg in validate_plan(mem.mat, mem.plan):
                    issues.append(f"member {i} ({mem.label}): {msg}")
    for t, inv in enumerate(comp._invs_np):
        inv = np.asarray(inv)
        if len(inv) != comp.n:
            issues.append(f"term {t}: inverse length {len(inv)} != "
                          f"n={comp.n}")
        else:
            stored = sum(mem.stored for mem in comp.members
                         if mem.term == t) + (1 if comp.pad_slot else 0)
            if len(inv) and (int(inv.min()) < 0
                             or int(inv.max()) >= stored):
                issues.append(f"term {t}: inverse indexes outside "
                              f"[0, {stored})")
    if issues and raise_:
        raise IntegrityError("; ".join(issues))
    return issues
