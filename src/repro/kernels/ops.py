"""Jit'd public wrappers around the Pallas SpMV kernels.

``packsell_spmv(mat, x)`` routes through the :mod:`repro.kernels.plan`
execution engine: a cached :class:`~repro.kernels.plan.SpMVPlan` carries the
host-side decisions (band feasibility/windows, tile parameters, kernel
variant) and a jitted dispatch function, so repeated matvecs never re-plan or
re-trace. The σ-permutation scatter (paper §4.4 line 15) is applied once over
the concatenated bucket outputs — or skipped entirely with ``permuted=True``.

Variant policy is explicit (logged in ``plan.policy``) and overridable via
``force=`` or the ``REPRO_SPMV_POLICY`` env var (``auto|fused|full|band|jnp``).

On non-TPU backends the Pallas kernels execute with ``interpret=True``
(kernel body evaluated in Python/XLA on CPU) — numerically identical, used by
the test suite to validate against the pure-jnp oracles in ``ref.py``. On a
TPU no Pallas body compiles yet (``plan.PALLAS_REFUSAL``), so ``auto`` runs
the XLA path there.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.packsell import PackSELLMatrix
from repro.core.sell import SELLMatrix
from . import plan as _plan
from . import sell_spmv as _sk

# Re-exported for band feasibility probing (tests, benchmarks).
band_plan = _plan.band_plan
_FULL_X_LIMIT = _plan._FULL_X_LIMIT
_DEF_HW = _plan._DEF_HW


def _debug_check_finite(x) -> None:
    """Opt-in input screen (``REPRO_DEBUG_FINITE=1``): reject NaN/Inf in
    x BEFORE it enters the packed kernels, where a poisoned entry smears
    into every output row touching its column. Host-side only — skipped
    for tracers (inside jit the guard layer owns detection)."""
    if os.environ.get("REPRO_DEBUG_FINITE", "0") != "1":
        return
    if isinstance(x, jax.core.Tracer):
        return
    xh = np.asarray(x)
    if not np.all(np.isfinite(xh)):
        bad = int(np.count_nonzero(~np.isfinite(xh)))
        raise FloatingPointError(
            f"packsell_spmv: input x has {bad} non-finite (NaN/Inf) "
            "entries (REPRO_DEBUG_FINITE=1)")


def packsell_spmv(mat: PackSELLMatrix, x: jnp.ndarray, *, sb: int = 8,
                  wb: int = 32, hw: int = _DEF_HW,
                  interpret: bool | None = None,
                  force: str | None = None,
                  decode_cache: str | None = None,
                  permuted: bool = False) -> jnp.ndarray:
    """y = A @ x via the plan engine (single jitted dispatch).

    ``force`` in {None, 'full', 'band', 'jnp'} pins the kernel variant;
    ``decode_cache`` in {None, 'checkpoint', 'full', '0'} pins the plan's
    decode-cache layout (default: ``REPRO_PLAN_CURSOR_CACHE``);
    ``permuted=True`` returns y in stored-row order (no σ-scatter).
    """
    _debug_check_finite(x)
    plan = _plan.get_plan(mat, sb=sb, wb=wb, hw=hw, force=force,
                          interpret=interpret, decode_cache=decode_cache)
    return plan.spmv(mat, x, permuted=permuted)


def packsell_spmm(mat: PackSELLMatrix, x: jnp.ndarray, *, sb: int = 8,
                  wb: int = 32, hw: int = _DEF_HW,
                  interpret: bool | None = None,
                  force: str | None = None,
                  decode_cache: str | None = None,
                  permuted: bool = False) -> jnp.ndarray:
    """Y = A @ X for X: [m, nb] via the multi-RHS kernel (one pass over the
    packed words for all nb right-hand sides)."""
    if x.ndim != 2:
        raise ValueError(f"packsell_spmm expects x of shape [m, nb], got "
                         f"{x.shape}; use packsell_spmv for a single RHS")
    plan = _plan.get_plan(mat, sb=sb, wb=wb, hw=hw, force=force,
                          interpret=interpret, decode_cache=decode_cache)
    return plan.spmm(mat, x, permuted=permuted)


def sell_spmv(mat: SELLMatrix, x: jnp.ndarray, *, sb: int = 8, wb: int = 32,
              interpret: bool | None = None) -> jnp.ndarray:
    interpret = _plan._interpret_default() if interpret is None else interpret
    parts = []
    for val, col in zip(mat.vals, mat.cols):
        t = _sk.sell_spmv_bucket(val, col, x, sb=sb, wb=wb,
                                 interpret=interpret)
        parts.append(t.reshape(-1))
    y = jnp.zeros((mat.n,), dtype=jnp.float32)
    if not parts:
        return y
    t_cat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    outrow_cat = jnp.concatenate([o.reshape(-1) for o in mat.outrows])
    return y.at[outrow_cat].set(t_cat, mode="drop")
