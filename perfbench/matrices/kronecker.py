"""The Graph500 Kronecker (R-MAT) generator, as a weighted undirected graph.

Follows the Graph500 specification's reference generator: for each of
``scale`` bit levels, every edge picks a quadrant with probabilities A, B,
C and D = 1 - A - B - C; then the vertex labels and the edge order are
randomly permuted. Edge weights are uniform in [0, 1), as in the Graph500
SSSP kernel. The adjacency matrix is made symmetric (W + W^T, so duplicate
edges add up) and self-loops are dropped.
"""
from __future__ import annotations

import numpy as np
import scipy.sparse as sp


def build(p: dict) -> sp.csr_matrix:
    scale, ef = int(p["scale"]), int(p["edgefactor"])
    a, b, c = float(p["A"]), float(p["B"]), float(p["C"])
    rng = np.random.default_rng(int(p["graph_seed"]))
    n = 1 << scale
    m = ef * n
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ii = np.zeros(m, np.int64)
    jj = np.zeros(m, np.int64)
    for bit in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ii |= ii_bit.astype(np.int64) << bit
        jj |= jj_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    ii, jj = perm[ii], perm[jj]
    order = rng.permutation(m)
    ii, jj = ii[order], jj[order]
    w = rng.random(m)
    keep = ii != jj
    ii, jj, w = ii[keep], jj[keep], w[keep]
    d = sp.csr_matrix((w, (ii, jj)), shape=(n, n))
    d.sum_duplicates()
    g = (d + d.T).tocsr()       # w_ij + w_ji on both sides: exactly symmetric
    g.sort_indices()
    return g
