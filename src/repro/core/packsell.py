"""The PackSELL sparse matrix format (paper §4) and its JAX SpMV.

Layout
------
Rows are σ-sorted (descending stored length, stable) within blocks of σ rows
(SELL-C-σ style, §4.3), then grouped into slices of C consecutive stored rows.
Each slice is padded to its max stored length with ``flag=0, delta=0`` words.

TPU adaptation (DESIGN.md §2): instead of a flat array + ``offset[]``
indirection, slices are grouped into **width buckets**: every slice's width is
rounded up to the bucket width so each bucket is a dense ``uint32[S, w, C]``
tensor. σ-sorting makes adjacent widths similar, so the extra padding is small
(reported in :meth:`PackSELLMatrix.memory_stats`), and the compute path gets
static shapes → static Pallas BlockSpecs and clean vectorization. Correctness
is unaffected because padding words are self-consistent.

The stored-row → original-row permutation is kept two ways: the paper-faithful
σ-local uint8 ``perm`` (for memory accounting and the implicit-permutation
story) and a precomputed int32 ``outrow`` gather map actually used on device.
"""
from __future__ import annotations

import dataclasses
import math
import os
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp

from repro.observe import metrics as _obs

from . import codecs as cd
from . import delta as de

PAD_WORD = np.uint32(0)  # flag=0, delta=0: contributes v=0, cursor unchanged


def _ceil_to(x: int, q: int) -> int:
    return (x + q - 1) // q * q


def _cumsum0(a: np.ndarray) -> np.ndarray:
    out = np.zeros(len(a) + 1, dtype=np.int64)
    np.cumsum(a, out=out[1:])
    return out


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackSELLMatrix:
    """Device-side PackSELL matrix. Registered as a pytree (jit-safe)."""

    # --- array leaves (device) ---
    packs: tuple          # tuple of uint32[S_b, w_b, C]
    d0s: tuple            # tuple of int32[S_b]      base column per slice
    outrows: tuple        # tuple of int32[S_b * C]  stored row -> orig row (n == drop)
    maxcols: tuple        # tuple of int32[S_b]      max column per slice (band kernel)
    perm: jnp.ndarray     # uint8/uint16[n_padded]   σ-local perm (paper-faithful)

    # --- static metadata ---
    n: int
    m: int
    C: int
    sigma: int
    D: int
    codec_name: str
    k_left: int
    nnz: int
    n_dummy: int
    words_sell_padded: int   # words if padded per-slice (paper layout)
    words_bucketed: int      # words actually stored (bucket layout)

    _STATIC = ("n", "m", "C", "sigma", "D", "codec_name", "k_left", "nnz",
               "n_dummy", "words_sell_padded", "words_bucketed")

    @property
    def codec(self) -> cd.Codec:
        return cd.make_codec(self.codec_name)

    @property
    def shape(self):
        return (self.n, self.m)

    def tree_flatten(self):
        leaves = (self.packs, self.d0s, self.outrows, self.maxcols, self.perm)
        aux = tuple(getattr(self, f) for f in self._STATIC)
        return leaves, aux

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        packs, d0s, outrows, maxcols, perm = leaves
        return cls(packs, d0s, outrows, maxcols, perm, *aux)

    # ------------------------------------------------------------------
    # memory accounting (paper Fig. 7 analogue)
    # ------------------------------------------------------------------
    def memory_stats(self) -> dict:
        n_slices = sum(int(p.shape[0]) for p in self.packs)
        perm_bytes = self.perm.size * self.perm.dtype.itemsize
        pack_bytes = 4 * self.words_sell_padded
        offset_bytes = 4 * (n_slices + 1)
        packsell = pack_bytes + offset_bytes + perm_bytes
        bucket_overhead = 4 * (self.words_bucketed - self.words_sell_padded)
        return dict(
            packsell_bytes=packsell,
            bucket_overhead_bytes=bucket_overhead,
            pack_bytes=pack_bytes,
            perm_bytes=perm_bytes,
            offset_bytes=offset_bytes,
            nnz=self.nnz,
            n_dummy=self.n_dummy,
            words_sell_padded=self.words_sell_padded,
            words_bucketed=self.words_bucketed,
        )

    # ------------------------------------------------------------------
    # SpMV (vectorized jnp path; the Pallas kernel mirrors this loop)
    # ------------------------------------------------------------------
    def spmv(self, x: jnp.ndarray, compute_dtype=jnp.float32) -> jnp.ndarray:
        return packsell_spmv_jnp(self, x, compute_dtype)

    def validate(self, *, raise_: bool = True) -> list:
        """Structural integrity check (robust.guard.validate_matrix):
        offset/outrow lengths and ranges, finite packed values, decoded
        column bounds, outrow bijectivity. Returns the list of problem
        strings (empty when clean); raises IntegrityError instead when
        ``raise_`` is set."""
        from repro.robust import guard as _guard
        return _guard.validate_matrix(self, raise_=raise_)


# Width-chunk for the scan decode: parallel within a chunk, cursor carried
# across chunks. Bounds the [S, chunk, C] intermediates so wide buckets stay
# cache-resident (the full-width scan loses its edge past a few hundred
# words); buckets narrower than the chunk decode in one shot.
_SCAN_CHUNK = int(os.environ.get("REPRO_SCAN_CHUNK", 128))


def _bucket_cols_scan(pack, d0, codec, D):
    """Scan-parallel column decode (DESIGN.md §2.4): cursors are prefix sums
    of the deltas, so all [S, w, C] columns come out of ONE associative scan
    (``cumsum`` over the width axis) instead of a sequential w-step word
    walk. Returns (value [S, w, C], col int32 [S, w, C])."""
    v, d = cd.unpack_words_jnp(pack, codec, D)
    cols = d0[:, None, None].astype(jnp.int32) + \
        jnp.cumsum(d.astype(jnp.int32), axis=1)
    return v, cols


def _bucket_spmv_scan(pack, d0, xc, codec, D, mlim, compute_dtype):
    """One bucket's stored-row outputs [S, C] via the cumsum decode: per
    width-chunk, one scan + one gather + one reduction (vs the loop decode's
    w sequential gather steps)."""
    S, w, C = pack.shape
    carry = jnp.broadcast_to(d0[:, None], (S, C)).astype(jnp.int32)
    t = jnp.zeros((S, C), dtype=compute_dtype)
    for j0 in range(0, w, _SCAN_CHUNK):
        pc = pack[:, j0:j0 + _SCAN_CHUNK, :]
        v, d = cd.unpack_words_jnp(pc, codec, D)
        cols = carry[:, None, :] + jnp.cumsum(d.astype(jnp.int32), axis=1)
        with _obs.span("packsell.x_gather"):
            xv = jnp.take(xc, jnp.minimum(cols, mlim).reshape(-1),
                          axis=0, mode="clip").reshape(cols.shape)
        t = t + jnp.sum(v.astype(compute_dtype) * xv, axis=1)
        carry = cols[:, -1, :]
    return t


def _bucket_spmv_loop(pack, d0, xc, codec, D, mlim, compute_dtype):
    """One bucket's stored-row outputs [S, C] via the sequential word walk
    (the paper's per-word recurrence; kept as the oracle/benchmark baseline
    for the scan decode)."""
    S, w, C = pack.shape
    c0 = jnp.broadcast_to(d0[:, None], (S, C)).astype(jnp.int32)
    t0 = jnp.zeros((S, C), dtype=compute_dtype)

    def body(j, carry):
        c, t = carry
        v, d = cd.unpack_words_jnp(pack[:, j, :], codec, D)
        c = c + d.astype(jnp.int32)
        with _obs.span("packsell.x_gather"):
            xv = jnp.take(xc, jnp.minimum(c, mlim), axis=0, mode="clip")
        t = t + v.astype(compute_dtype) * xv
        return c, t

    _, t = jax.lax.fori_loop(0, w, body, (c0, t0))
    return t


def _bucket_spmm_scan(pack, d0, xc, codec, D, mlim, compute_dtype):
    """Multi-RHS bucket outputs [S, C, nb] via the chunked cumsum decode."""
    S, w, C = pack.shape
    nb = xc.shape[1]
    carry = jnp.broadcast_to(d0[:, None], (S, C)).astype(jnp.int32)
    t = jnp.zeros((S, C, nb), dtype=compute_dtype)
    for j0 in range(0, w, _SCAN_CHUNK):
        pc = pack[:, j0:j0 + _SCAN_CHUNK, :]
        v, d = cd.unpack_words_jnp(pc, codec, D)
        cols = carry[:, None, :] + jnp.cumsum(d.astype(jnp.int32), axis=1)
        with _obs.span("packsell.x_gather"):
            xv = jnp.take(xc, jnp.minimum(cols, mlim).reshape(-1),
                          axis=0, mode="clip").reshape(cols.shape + (nb,))
        t = t + jnp.sum(v.astype(compute_dtype)[..., None] * xv, axis=1)
        carry = cols[:, -1, :]
    return t


def _bucket_spmm_loop(pack, d0, xc, codec, D, mlim, compute_dtype):
    S, w, C = pack.shape
    nb = xc.shape[1]
    c0 = jnp.broadcast_to(d0[:, None], (S, C)).astype(jnp.int32)
    t0 = jnp.zeros((S, C, nb), dtype=compute_dtype)

    def body(j, carry):
        c, t = carry
        v, d = cd.unpack_words_jnp(pack[:, j, :], codec, D)
        c = c + d.astype(jnp.int32)
        with _obs.span("packsell.x_gather"):
            xv = jnp.take(xc, jnp.minimum(c, mlim).reshape(-1),
                          axis=0, mode="clip").reshape(S, C, nb)
        t = t + v.astype(compute_dtype)[..., None] * xv
        return c, t

    _, t = jax.lax.fori_loop(0, w, body, (c0, t0))
    return t


def packsell_spmv_jnp(mat: PackSELLMatrix, x: jnp.ndarray,
                      compute_dtype=jnp.float32,
                      decode: str = "scan") -> jnp.ndarray:
    """y = A @ x over the bucketed PackSELL layout (paper §4.4 algorithm).

    The per-word recurrence is exactly the paper's: unpack → advance column
    cursor by delta → fused multiply-accumulate. Padding and dummy words
    contribute v = 0 so no masking is required.

    ``decode='scan'`` (default) decodes all column cursors in one
    associative prefix-sum over the width axis; ``decode='loop'`` keeps the
    sequential ``fori_loop`` word walk (benchmark baseline).
    """
    body = {"scan": _bucket_spmv_scan, "loop": _bucket_spmv_loop}[decode]
    codec = mat.codec
    mlim = np.int32(max(mat.m - 1, 0))
    y = jnp.zeros((mat.n,), dtype=compute_dtype)
    xc = x.astype(compute_dtype)
    for pack, d0, outrow in zip(mat.packs, mat.d0s, mat.outrows):
        t = body(pack, d0, xc, codec, mat.D, mlim, compute_dtype)
        y = y.at[outrow].set(t.reshape(-1), mode="drop")
    return y


def packsell_spmm_jnp(mat: PackSELLMatrix, x: jnp.ndarray,
                      compute_dtype=jnp.float32,
                      decode: str = "scan") -> jnp.ndarray:
    """Y = A @ X for X: [m, nb] (multi-RHS SpMV; block-Krylov / batched
    pruned-weight serving). One pass over the packed words serves all nb
    right-hand sides — nb× arithmetic intensity vs nb separate SpMVs,
    which is exactly how the memory-bound regime wants it."""
    body = {"scan": _bucket_spmm_scan, "loop": _bucket_spmm_loop}[decode]
    codec = mat.codec
    nb = x.shape[1]
    mlim = np.int32(max(mat.m - 1, 0))
    y = jnp.zeros((mat.n, nb), dtype=compute_dtype)
    xc = x.astype(compute_dtype)
    for pack, d0, outrow in zip(mat.packs, mat.d0s, mat.outrows):
        S, w, C = pack.shape
        t = body(pack, d0, xc, codec, mat.D, mlim, compute_dtype)
        y = y.at[outrow].set(t.reshape(S * C, nb), mode="drop")
    return y


# ---------------------------------------------------------------------------
# Construction
# ---------------------------------------------------------------------------


def _sigma_sort(stored_len: np.ndarray, n: int, sigma: int, C: int):
    """σ-block stable descending sort. Returns (outrow, perm_local).

    outrow[stored_idx] = original row (len n_padded, sentinel n for padding
    rows); perm_local[stored_idx] = original index within the σ-block.
    """
    n_padded = _ceil_to(max(n, 1), C)
    outrow = np.full(n_padded, n, dtype=np.int64)
    for b0 in range(0, n, sigma):
        b1 = min(b0 + sigma, n)
        order = np.argsort(-stored_len[b0:b1], kind="stable")
        outrow[b0:b1] = b0 + order
    perm_dtype = np.uint8 if sigma <= 256 else np.uint16
    perm_local = (outrow[:n] - (np.arange(n) // sigma) * sigma).astype(perm_dtype)
    pad_perm = np.zeros(n_padded - n, dtype=perm_dtype)
    return outrow, np.concatenate([perm_local, pad_perm])


def _bucket_slices(widths: np.ndarray, strategy: str):
    """Group slice ids into width buckets.

    'pow2'    : bucket width = next power of two (small, bounded padding)
    'uniform' : a single bucket at max width (simplest kernels)
    'exact'   : one bucket per distinct width (zero bucket padding)
    """
    S = len(widths)
    if S == 0:
        return []
    if strategy == "uniform":
        wmax = int(widths.max())
        return [(np.arange(S), max(wmax, 1))]
    if strategy == "pow2":
        keys = np.where(widths <= 1, 1,
                        2 ** np.ceil(np.log2(np.maximum(widths, 1))).astype(np.int64))
    elif strategy == "exact":
        keys = np.maximum(widths, 1)
    else:
        raise ValueError(strategy)
    out = []
    for k in np.unique(keys):
        ids = np.nonzero(keys == k)[0]
        out.append((ids, int(k)))
    return out


def from_csr(a: sp.csr_matrix, *, C: int = 128, sigma: int = 256, D: int = 15,
             codec: str = "fp16", bucket_strategy: str = "pow2",
             device: bool = True) -> PackSELLMatrix:
    """Build a PackSELL matrix from a scipy CSR matrix (host span
    ``packsell.pack``, with a child span per stage)."""
    with _obs.host_span("packsell.pack"):
        return _from_csr(a, C=C, sigma=sigma, D=D, codec=codec,
                         bucket_strategy=bucket_strategy, device=device)


def _from_csr(a, *, C, sigma, D, codec, bucket_strategy, device):
    if sigma % C != 0:
        raise ValueError(f"sigma ({sigma}) must be a multiple of C ({C})")
    a = a.tocsr()
    a.sort_indices()
    n, m = a.shape
    indptr = a.indptr.astype(np.int64)
    indices = a.indices.astype(np.int64)
    if a.nnz and not np.all(np.isfinite(a.data)):
        bad = int(np.count_nonzero(~np.isfinite(a.data)))
        raise ValueError(
            f"from_csr: input has {bad} non-finite (NaN/Inf) values; "
            "packed codecs cannot represent them")
    if a.nnz and (indices.min() < 0 or indices.max() >= m):
        raise ValueError(
            f"from_csr: column indices outside [0, {m}) "
            f"(min {int(indices.min())}, max {int(indices.max())})")
    values = a.data.astype(np.float32)
    codec_obj = cd.make_codec(codec)
    if not (codec_obj.min_D <= D <= codec_obj.max_D):
        raise ValueError(f"D={D} outside [{codec_obj.min_D},{codec_obj.max_D}] "
                         f"for codec {codec}")

    with _obs.host_span("packsell.pack.encode"):
        k_left = de.lower_bandwidth(indptr, indices, n)
        d0_row = de.d0_for_rows(n, sigma, k_left)
        deltas, n_dummies, stored_len = de.encode_rows(indptr, indices,
                                                       d0_row, D)
    with _obs.host_span("packsell.pack.words"):
        w_values, w_deltas, w_flags, _, n_words = de.emit_word_stream(
            values, deltas, n_dummies)
        words = cd.pack_words_np(w_values, w_deltas, w_flags, codec_obj, D)
    row_word_start = _cumsum0(stored_len)

    with _obs.host_span("packsell.pack.slices"):
        outrow, perm = _sigma_sort(stored_len, n, sigma, C)
        n_padded = len(outrow)
        S = n_padded // C

        stored_len_padded = np.zeros(n_padded, dtype=np.int64)
        valid = outrow < n
        stored_len_padded[valid] = stored_len[outrow[valid]]
        slice_width = stored_len_padded.reshape(S, C).max(axis=1)
        words_sell_padded = int((slice_width * C).sum())

        d0_slice = np.maximum((np.arange(S) * C // sigma) * sigma - k_left,
                              0)

        # per-row last column (band-kernel window metadata); empty rows ->
        # d0
        lastcol_row = d0_row.copy()
        nz_rows = np.diff(indptr) > 0
        lastcol_row[nz_rows] = indices[indptr[1:][nz_rows] - 1]
        lastcol_padded = np.zeros(n_padded, dtype=np.int64)
        lastcol_padded[valid] = lastcol_row[outrow[valid]]
        maxcol_slice = lastcol_padded.reshape(S, C).max(axis=1)

        buckets = _bucket_slices(slice_width, bucket_strategy)
        packs, d0s, outrows, maxcols_l = [], [], [], []
        words_bucketed = 0
        # guard row for the gather below (padding rows index word 0
        # harmlessly)
        words_g = words if n_words > 0 else np.zeros(1, dtype=np.uint32)
        for slice_ids, w_b in buckets:
            rows = (slice_ids[:, None] * C
                    + np.arange(C)[None, :]).reshape(-1)
            orig = outrow[rows]                         # [S_b*C]
            lens = stored_len_padded[rows]              # [S_b*C]
            starts = np.where(orig < n,
                              row_word_start[np.minimum(orig, n - 1)], 0)
            j = np.arange(w_b, dtype=np.int64)
            idx = starts[:, None] + j[None, :]          # [S_b*C, w_b]
            ok = j[None, :] < lens[:, None]
            gathered = np.where(
                ok, words_g[np.minimum(idx, len(words_g) - 1)], PAD_WORD)
            pack3d = gathered.reshape(len(slice_ids), C,
                                      w_b).transpose(0, 2, 1)
            packs.append(np.ascontiguousarray(pack3d.astype(np.uint32)))
            d0s.append(d0_slice[slice_ids].astype(np.int32))
            outrows.append(np.where(orig < n, orig, n).astype(np.int32))
            maxcols_l.append(maxcol_slice[slice_ids].astype(np.int32))
            words_bucketed += pack3d.size

    with _obs.host_span("packsell.pack.to_device"):
        to_dev = jnp.asarray if device else (lambda v: v)
        mat = PackSELLMatrix(
            packs=tuple(to_dev(p) for p in packs),
            d0s=tuple(to_dev(d) for d in d0s),
            outrows=tuple(to_dev(o) for o in outrows),
            maxcols=tuple(to_dev(mc) for mc in maxcols_l),
            perm=to_dev(perm),
            n=n, m=m, C=C, sigma=sigma, D=D, codec_name=codec,
            k_left=k_left,
            nnz=int(a.nnz), n_dummy=int(n_dummies.sum()),
            words_sell_padded=words_sell_padded,
            words_bucketed=int(words_bucketed),
        )
    return mat


def from_dense(a: np.ndarray, **kw) -> PackSELLMatrix:
    a = np.asarray(a)
    if a.ndim != 2:
        raise ValueError(f"from_dense: expected a 2-D array, got shape "
                         f"{a.shape}")
    if not np.all(np.isfinite(a)):
        bad = int(np.count_nonzero(~np.isfinite(a)))
        raise ValueError(
            f"from_dense: input has {bad} non-finite (NaN/Inf) values; "
            "packed codecs cannot represent them")
    return from_csr(sp.csr_matrix(a), **kw)


# ---------------------------------------------------------------------------
# Per-partition build hooks (distributed layer, DESIGN.md §7)
# ---------------------------------------------------------------------------


def pad_uniform(mat: PackSELLMatrix, *, n_slices: int | None = None,
                width: int | None = None, n_rows: int | None = None,
                device: bool = True) -> PackSELLMatrix:
    """Pad a single-bucket ('uniform') matrix to a common [S, w, C] shape.

    The distributed partitioner σ-sorts and builds each shard's block
    independently (per-partition sorting keeps padding low, SELL-C-σ §3),
    which leaves every shard with different slice counts and widths. SPMD
    dispatch under ``shard_map`` needs one static shape for all shards, so
    each block is padded here to the fleet-wide maxima: extra words are
    ``PAD_WORD`` (flag=0, delta=0 → contribute nothing), extra slices get
    sentinel outrows (dropped / masked), and ``n`` grows to ``n_rows`` with
    the old sentinel value remapped so padding rows stay dead.
    """
    if len(mat.packs) != 1:
        raise ValueError("pad_uniform needs a single-bucket matrix "
                         "(build with bucket_strategy='uniform')")
    pack = np.asarray(mat.packs[0])
    d0 = np.asarray(mat.d0s[0])
    outrow = np.asarray(mat.outrows[0])
    maxcol = np.asarray(mat.maxcols[0])
    perm = np.asarray(mat.perm)
    S, w, C = pack.shape
    S_t = S if n_slices is None else int(n_slices)
    w_t = w if width is None else int(width)
    n_t = mat.n if n_rows is None else int(n_rows)
    if S_t < S or w_t < w or n_t < mat.n:
        raise ValueError(f"cannot shrink: have (S={S}, w={w}, n={mat.n}), "
                         f"asked (S={S_t}, w={w_t}, n={n_t})")
    if S_t * C < n_t:
        raise ValueError(f"S={S_t} slices of C={C} cannot hold n={n_t} rows")

    pack_p = np.full((S_t, w_t, C), PAD_WORD, dtype=np.uint32)
    pack_p[:S, :w, :] = pack
    d0_p = np.zeros(S_t, np.int32)
    d0_p[:S] = d0
    maxcol_p = np.zeros(S_t, np.int32)
    maxcol_p[:S] = maxcol
    # remap the old padding sentinel (== mat.n) to the new one (== n_t)
    outrow_p = np.full(S_t * C, n_t, np.int32)
    outrow_p[:S * C] = np.where(outrow >= mat.n, n_t, outrow)
    # give every padding row a stored slot of its own, carved out of the
    # sentinel (all-PAD-word) slots: those columns decode to exactly 0, so
    # padding rows stay dead through BOTH epilogue forms — the scatter
    # (sentinel drop) and the plan engine's inverse-permutation *gather*,
    # which requires one slot per row (kernels/plan.py::_build_inverse_perm)
    sentinel = np.nonzero(outrow_p >= n_t)[0]
    extra = n_t - mat.n
    outrow_p[sentinel[:extra]] = mat.n + np.arange(extra, dtype=np.int32)
    perm_p = np.zeros(S_t * C, perm.dtype)
    perm_p[:len(perm)] = perm

    to_dev = jnp.asarray if device else (lambda v: v)
    return PackSELLMatrix(
        packs=(to_dev(pack_p),), d0s=(to_dev(d0_p),),
        outrows=(to_dev(outrow_p),), maxcols=(to_dev(maxcol_p),),
        perm=to_dev(perm_p),
        n=n_t, m=mat.m, C=C, sigma=mat.sigma, D=mat.D,
        codec_name=mat.codec_name, k_left=mat.k_left, nnz=mat.nnz,
        n_dummy=mat.n_dummy, words_sell_padded=mat.words_sell_padded,
        words_bucketed=int(pack_p.size),
    )


def aggregate_memory_stats(mats: Sequence[PackSELLMatrix]) -> dict:
    """Fleet-level :meth:`PackSELLMatrix.memory_stats`: per-shard sums plus
    the max/min shard footprint (load-balance signal for the partitioner)."""
    stats = [m.memory_stats() for m in mats]
    agg = {k: sum(s[k] for s in stats) for k in stats[0]} if stats else {}
    per_shard = [s["packsell_bytes"] for s in stats]
    agg["shards"] = len(stats)
    agg["max_shard_bytes"] = max(per_shard) if per_shard else 0
    agg["min_shard_bytes"] = min(per_shard) if per_shard else 0
    return agg


# ---------------------------------------------------------------------------
# Host-side decode (oracle for tests)
# ---------------------------------------------------------------------------


def decode_to_dense(mat: PackSELLMatrix) -> np.ndarray:
    """Reconstruct the (quantized) dense matrix by walking the packed words."""
    codec = mat.codec
    out = np.zeros((mat.n, mat.m), dtype=np.float64)
    for pack, d0, outrow in zip(mat.packs, mat.d0s, mat.outrows):
        pack = np.asarray(pack)
        d0 = np.asarray(d0)
        outrow = np.asarray(outrow)
        S, w, C = pack.shape
        v, d, flag = cd.unpack_words_np(pack.reshape(-1), codec, mat.D)
        v = v.astype(np.float64).reshape(S, w, C)
        d = d.astype(np.int64).reshape(S, w, C)
        flag = flag.reshape(S, w, C)
        cols = d0[:, None, None] + np.cumsum(d, axis=1)
        rows = outrow.reshape(S, C)
        for s in range(S):
            for l in range(C):
                r = rows[s, l]
                if r >= mat.n:
                    continue
                sel = flag[s, :, l] == 1
                out[r, cols[s, sel, l]] += v[s, sel, l]
    return out
