"""The plain reference, and the comparisons that decide ``correct``.

Nothing here imports the program under test. The stored operator is
rebuilt from the CSR by rounding each value to the precision the
configuration states (``precision.values``), with NumPy and ml_dtypes
alone, and every product and sum is then taken in float64 by scipy.

* SpMV: ``y_gap`` is the largest row error of the program's y against
  ``A_q x``, relative to that row's ``(|A_q| |x|)``; on an empty row the
  error is absolute (y must be exactly 0 there).
* PCG: the reference runs the same Jacobi-PCG recurrence in float64 on
  ``A_q`` for the same number of iterations. ``x_gap`` is
  ``||x - x_ref|| / ||x_ref||`` and ``relres_gap`` is
  ``|relres - relres_ref| / relres_ref``, relres being the recurrence's
  ``||r|| / ||b||``. ``x_f32_share`` is the share of x's entries that
  float32 holds exactly: about 0 for float64 solver vectors, 1 where x
  was computed in float32.

A control puts this reference in the program's place with one of the
configuration's stated precisions one step lower (its ``control``): the
values (``stored_operator`` at the lower precision) or x
(``round_values``).
"""
from __future__ import annotations

import ml_dtypes
import numpy as np
import scipy.sparse as sp

_FLOATS = {
    "float64": np.float64,
    "float32": np.float32,
    "float16": np.float16,
    "bfloat16": ml_dtypes.bfloat16,
    "float8_e4m3fn": ml_dtypes.float8_e4m3fn,
}


def _round_mantissa(v32: np.ndarray, mbits: int) -> np.ndarray:
    """Round float32 values to ``mbits`` mantissa bits, to nearest even
    (finite values only)."""
    low = 23 - mbits
    u = np.ascontiguousarray(v32, np.float32).view(np.uint32)
    lsb = (u >> np.uint32(low)) & np.uint32(1)
    half = np.uint32((1 << (low - 1)) - 1)
    r = (u + half + lsb) & ~np.uint32((1 << low) - 1)
    return r.view(np.float32)


def round_values(vals: np.ndarray, precision: str) -> np.ndarray:
    """``vals`` (float64) as a float32 input rounded to ``precision``,
    returned in float64. ``e8m<Y>`` is sign, 8 exponent bits and Y
    mantissa bits: float32 rounded to Y mantissa bits."""
    v32 = np.asarray(vals, np.float64).astype(np.float32)
    if precision.startswith("e8m"):
        return _round_mantissa(v32, int(precision[3:])).astype(np.float64)
    if precision not in _FLOATS:
        raise KeyError(f"unknown value precision {precision!r}; known: "
                       f"{sorted(_FLOATS)} and e8m<Y>")
    return v32.astype(_FLOATS[precision]).astype(np.float64)


def stored_operator(a: sp.csr_matrix, precision: str) -> sp.csr_matrix:
    aq = a.copy().astype(np.float64)
    aq.data = round_values(a.data, precision)
    return aq


class SpMVReference:
    """``A_q`` and ``|A_q|`` of one run, with the gap of a program y."""

    def __init__(self, a: sp.csr_matrix, precision: str):
        self.aq = stored_operator(a, precision)
        self.aq_abs = abs(self.aq)

    def solve(self, x: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        x64 = np.asarray(x, np.float64)
        return self.aq @ x64, self.aq_abs @ np.abs(x64)

    @staticmethod
    def gap(y: np.ndarray, ref: np.ndarray, scale: np.ndarray) -> float:
        y = np.asarray(y, np.float64)
        if y.shape != ref.shape or not np.all(np.isfinite(y)):
            return float("inf")
        diff = np.abs(y - ref)
        rel = np.where(scale > 0, diff / np.where(scale > 0, scale, 1.0),
                       diff)
        return float(rel.max()) if rel.size else 0.0


def pcg(a: sp.csr_matrix, diag: np.ndarray, b: np.ndarray,
        iters: int) -> tuple[np.ndarray, float]:
    """Jacobi-PCG from x0 = 0 for exactly ``iters`` iterations, in float64.
    Returns ``(x, ||r|| / ||b||)`` of the recurrence."""
    b = np.asarray(b, np.float64)
    dinv = np.where(diag == 0, 1.0, 1.0 / diag)
    x = np.zeros_like(b)
    r = b.copy()
    z = r * dinv
    p = z.copy()
    rz = r @ z
    for _ in range(iters):
        ap = a @ p
        pap = p @ ap
        alpha = rz / (pap if pap != 0 else 1.0)
        x += alpha * p
        r -= alpha * ap
        z = r * dinv
        rz_new = r @ z
        beta = rz_new / (rz if rz != 0 else 1.0)
        p = z + beta * p
        rz = rz_new
    return x, float(np.linalg.norm(r) / np.linalg.norm(b))


def pcg_gaps(x: np.ndarray, relres: float, x_ref: np.ndarray,
             relres_ref: float) -> dict:
    x = np.asarray(x, np.float64)
    if x.shape != x_ref.shape or not np.all(np.isfinite(x)) \
            or not np.isfinite(relres):
        return {"x_gap": float("inf"), "relres_gap": float("inf"),
                "x_f32_share": 1.0}
    return {"x_gap": float(np.linalg.norm(x - x_ref)
                           / np.linalg.norm(x_ref)),
            "relres_gap": float(abs(relres - relres_ref) / relres_ref),
            "x_f32_share": float(np.mean(
                x.astype(np.float32).astype(np.float64) == x))}
