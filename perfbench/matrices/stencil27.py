"""HPCG's 27-point operator on an nx x ny x nz grid, symmetrically scaled.

The HPCG reference code (``GenerateProblem``) puts 26 on the diagonal and
-1 on each of the up to 26 neighbours of a grid point. ``sym_scale``
divides row and column i by sqrt(a_ii), as the PackSELL paper does before
its solver runs (section 5.2), so the scaled diagonal is 1.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def build(p: dict) -> sp.csr_matrix:
    nx, ny, nz = int(p["nx"]), int(p["ny"]), int(p["nz"])
    n = nx * ny * nz
    idx = np.arange(n)
    iz, iy, ix = idx // (nx * ny), (idx // nx) % ny, idx % nx
    rows, cols = [], []
    for dz in (-1, 0, 1):
        for dy in (-1, 0, 1):
            for dx in (-1, 0, 1):
                jx, jy, jz = ix + dx, iy + dy, iz + dz
                ok = ((jx >= 0) & (jx < nx) & (jy >= 0) & (jy < ny)
                      & (jz >= 0) & (jz < nz))
                rows.append(idx[ok])
                cols.append((jz[ok] * ny + jy[ok]) * nx + jx[ok])
    rows = np.concatenate(rows)
    cols = np.concatenate(cols)
    vals = np.where(rows == cols, 26.0, -1.0)
    a = sp.csr_matrix((vals, (rows, cols)), shape=(n, n))
    a.sort_indices()
    if p.get("sym_scale", False):
        d = np.sqrt(np.abs(a.diagonal()))
        d = np.where(d == 0, 1.0, d)
        dinv = sp.diags(1.0 / d)
        a = (dinv @ a @ dinv).tocsr()
        a.sort_indices()
    return a
