"""Windowed x gather of the jnp decode bodies (DESIGN.md §10.5).

The decode bodies read ``x[col]`` for every stored word. On a TPU an XLA
scalar gather costs per index (about 8.7 ns, PERF.md §6), whatever the
bytes, while a row gather of a 2-D operand costs a few ns per *row*. The
C lanes of one (group, word) pair are C neighbouring stored rows reading
the same stencil offset, so their columns nearly always lie in one short
window of x. This module turns the per-word gather into one row gather
per pair plus an exact per-lane pick:

* **host** (plan build): every pair is classified by the span of its real
  words' columns (:func:`pair_spans`); a cost model picks the window
  width W from that histogram (:func:`pick_window_width`, W = 0 keeps the
  scalar gather); :func:`window_operands` gives each pair of a part (the
  fused stream, or one bucket of the cursor cache) its window row and
  each lane its offset in it, and the residual pairs, whose span is too
  wide, their columns; :func:`build_window` gives each part ``wbase``
  and ``woff`` laid out as its pick kernel reads them, and all parts one
  ``wres int32[C·P_r]``.
* **device** (:func:`window_gather`): the residual columns are gathered
  into an extended x, which is cut into overlapping rows of W entries
  starting every ``S = W/8`` entries, once per call for all parts; one
  row per pair is gathered and each lane picks its value by ``woff``
  with a compare-select chain — a Mosaic kernel per part (XLA would
  split the chain into one pass per window position; off a TPU the
  Pallas interpreter runs the same kernel).

Every lane a product depends on reads exactly the entry the scalar gather
reads, so the SpMV result is bit-identical to the scalar-gather path.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

#: x-gather cost model, nanoseconds on one TPU v5e, fitted to whole-SpMV
#: times at HPCG 104³ for every W (PERF.md §6). The plan
#: prices each window width W against the scalar gather (W = 0) from the
#: matrix's own span histogram and keeps the cheapest; nothing else
#: decides the path.
SCALAR_INDEX_NS = 8.7        # one index of the XLA scalar gather of x
#: one (group, word) pair's row gather plus its lane pick, by W. W = 32
#: and 64 were fitted too (43.0 and 11.4 ns: a row narrower than the
#: 128-lane vreg row wastes it) and are not offered: every pair they
#: hold fits W = 128, at a third of the cost or less, which outweighs
#: its larger table for any C unless x has ten times more entries than
#: stored words
WINDOW_PAIR_NS = {128: 3.6, 256: 7.4}
TABLE_NS = 0.011             # one entry of the overlapping-row table
WINDOW_WIDTHS = tuple(WINDOW_PAIR_NS)
#: a W-wide window starts every W / ROW_COPIES entries of x, so the
#: overlapping-row table holds ROW_COPIES copies of x; a pair fits its
#: window when ``lo % (W / ROW_COPIES) + span < W``
ROW_COPIES = 8
_PICK_BLOCK = 512            # pairs (lanes of the pick kernel) per grid step
PICK_KERNEL = "xgather_pick"  # the pick kernel's name in HLO and traces


def stride(W: int, C: int) -> int:
    """Window start granularity S of W-wide windows over C-lane pairs
    (divides C, so a residual pair's slot starts a table row)."""
    return math.gcd(max(W // ROW_COPIES, 1), C)


def pair_spans(cols, free):
    """Per (group, word) pair of C lanes, from ``cols``/``free`` shaped
    ``[..., C]``: ``(lo_r, lo_all, span_r, span_all)`` — the lowest real
    column, the lowest column of any lane, and the spans (highest minus
    lowest column) of the real lanes and of all lanes. ``free`` marks
    lanes whose product does not depend on the x entry read (±0 words); a
    pair of only free lanes has real span 0 at its lowest column."""
    C = cols.shape[-1]
    c = cols.reshape(-1, C)
    f = free.reshape(-1, C)
    lo_all, hi_all = c.min(axis=1), c.max(axis=1)
    lo_r, hi_r = lo_all.copy(), hi_all.copy()
    i = np.flatnonzero(f.any(axis=1))        # the pairs with a free lane
    cf, ff = c[i], f[i]
    lo_r[i] = np.where(ff, np.iinfo(np.int32).max, cf).min(axis=1)
    hi_r[i] = np.where(ff, -1, cf).max(axis=1)
    empty = hi_r < 0
    lo_r = np.where(empty, lo_all, lo_r)
    span_r = np.where(empty, 0, hi_r - lo_r)
    return lo_r, lo_all, span_r, hi_all - lo_all


def fits(lo, span, W: int, S: int):
    """Whether columns ``lo .. lo + span`` lie in the W-window that
    starts at the S-aligned entry at or below ``lo``."""
    return lo % S + span < W


def window_cost_ns(n_pairs: int, n_resid: int, W: int, C: int,
                   table: int) -> float:
    """Modeled x-gather time (ns) of one call: W = 0 is the scalar gather
    of every lane; otherwise one window row and lane pick per pair, the
    residual pairs' scalar gather and the overlapping-row table, W/S
    copies of the ``table`` entries of the extended x."""
    if W == 0:
        return n_pairs * C * SCALAR_INDEX_NS
    return (n_pairs * WINDOW_PAIR_NS[W] + n_resid * C * SCALAR_INDEX_NS
            + table * (W // stride(W, C)) * TABLE_NS)


def _residual_pairs(spans, W: int, C: int) -> int:
    """Pairs of one part (its :func:`pair_spans`) that no W-window holds."""
    return int(np.count_nonzero(~fits(spans[0], spans[2], W, stride(W, C))))


def pick_window_width(spans, C: int, m: int) -> int:
    """The window width in :data:`WINDOW_WIDTHS` that minimises
    :func:`window_cost_ns` over all parts (``spans``: one
    :func:`pair_spans` result per part, sharing one table), or 0 when the
    scalar gather is cheapest. Only W >= C can hold a residual pair's C
    values."""
    pairs = sum(len(s[2]) for s in spans)
    best, best_cost = 0, window_cost_ns(pairs, 0, 0, C, 0)
    for W in WINDOW_WIDTHS:
        if W < C:
            continue
        resid = sum(_residual_pairs(s, W, C) for s in spans)
        cost = window_cost_ns(pairs, resid, W, C, m + resid * C)
        if cost < best_cost:
            best, best_cost = W, cost
    return best


def window_operands(cols, spans, W: int, m: int, slot0: int = 0):
    """One part's ``(wbase, woff, wres)`` at window width W (host arrays).

    ``wbase`` is each pair's window row: the window covers x entries
    ``S·wbase .. S·wbase + W - 1`` of the extended x; ``woff`` is each
    lane's offset in it. A pair reads the window of its lowest column of
    any lane when every lane fits (every lane then reads what the scalar
    gather reads), else the window of its lowest real column, where a
    free lane outside the window reads offset 0. The k-th residual pair
    reads its C values at entry ``mb + C·(slot0 + k)`` of the extended x
    (``mb`` being m rounded up to S; ``slot0`` the residual pairs of the
    parts before this one): its columns go to ``wres``, which the
    dispatch gathers there."""
    lo_r, lo_all, span_r, span_all = spans
    shape = cols.shape
    C = shape[-1]
    S = stride(W, C)
    c = np.asarray(cols, np.int32).reshape(-1, C)
    start = np.where(fits(lo_all, span_all, W, S), lo_all, lo_r) // S * S
    off = c - start[:, None]
    off = np.where(off.view(np.uint32) < W, off, 0).astype(np.uint8)
    resid = np.flatnonzero(~fits(lo_r, span_r, W, S))
    start[resid] = -(-m // S) * S + C * (slot0 + np.arange(len(resid)))
    off[resid] = np.arange(C)
    return ((start // S).astype(np.int32).reshape(shape[:-1]),
            off.reshape(shape), c[resid].astype(np.int32).reshape(-1))


def build_window(parts, m: int):
    """``(W, (wres, ((wbase, woff), ...)))`` for the x gathers of a plan,
    or ``(0, None)`` where the cost model keeps the scalar gather.
    ``parts`` is one ``(cols, free)`` pair per gather (the fused stream,
    or each bucket of the cursor cache), shaped ``[G, wr, C]``. All parts
    read one table, whose residual slots ``wres`` fills part after part;
    each part's :func:`window_operands` are laid out as its pick kernel
    reads them (:func:`kernel_operands`)."""
    if not parts or m == 0:
        return 0, None
    C = parts[0][0].shape[-1]
    spans = [pair_spans(c, f) for c, f in parts]
    W = pick_window_width(spans, C, m)
    if W == 0:
        return 0, None
    ops, res, slot = [], [], 0
    for (c, _), s in zip(parts, spans):
        base, off, r = window_operands(c, s, W, m, slot)
        ops.append(kernel_operands(base, off))
        res.append(r)
        slot += r.shape[0] // C
    return W, (np.concatenate(res), tuple(ops))


def pick_block(G: int) -> int:
    """Pairs per grid step of the pick kernel over a part of G groups."""
    return min(_PICK_BLOCK, -(-G // 128) * 128)


def kernel_operands(base, off):
    """One part's ``wbase [G, wr]`` and ``woff [G, wr, C]`` as its pick
    kernel reads them, G padded to Gp, a multiple of :func:`pick_block`:
    ``wbase int32[wr·Gp]`` word-major and ``woff uint8[wr, C, Gp]``, pairs
    on lanes — the layout of the kernel's output, which the decode's
    product then reads with no copy."""
    G, wr, C = off.shape
    bg = pick_block(G)
    Gp = -(-G // bg) * bg
    wbase = np.zeros((wr, Gp), np.int32)
    wbase[:, :G] = base.T
    woff = np.zeros((wr, C, Gp), np.uint8)
    woff[:, :, :G] = off.transpose(1, 2, 0)
    return wbase.reshape(-1), woff


# ---------------------------------------------------------------------------
# Device side
# ---------------------------------------------------------------------------


def window_table(xc, wres, W: int, C: int):
    """``rows[i] = x_ext[S·i : S·i + W]`` — the overlapping-row table of
    the extended x (x, zero padding to a multiple of S, the residual
    pairs' gathered values, zero padding). Built from the aligned W-wide
    rows of x_ext, each residue ``r`` of ``i mod W/S`` a lane shift of
    two neighbouring rows, so no piece is narrower than a window."""
    S = stride(W, C)
    m = xc.shape[0]
    mb = -(-m // S) * S
    n_ext = -(-(mb + wres.shape[0] + W) // W) * W
    x_ext = jnp.concatenate([
        xc, jnp.zeros((mb - m,), xc.dtype),
        jnp.take(xc, wres, axis=0, mode="clip"),
        jnp.zeros((n_ext - mb - wres.shape[0],), xc.dtype)])
    a = x_ext.reshape(-1, W)
    K = W // S
    rows = jnp.stack([jnp.concatenate([a[:-1, S * r:], a[1:, :S * r]],
                                      axis=1) for r in range(K)], axis=1)
    return rows.reshape(-1, W)


def _pick_kernel(win_ref, off_ref, out_ref):
    """One grid step: ``out[c, g] = win[g, off[c, g]]`` for a block of
    pairs, pairs on lanes (the window block is transposed in VMEM, so
    each window position is one sublane-broadcast row)."""
    wt = win_ref[...].T                                  # [W, bg]
    o = off_ref[0].astype(jnp.int32)                     # [C, bg]
    acc = jnp.broadcast_to(wt[0:1, :], o.shape)
    for k in range(1, wt.shape[0]):
        acc = jnp.where(o == k, wt[k:k + 1, :], acc)
    out_ref[0] = acc


def _pick(rows, wbase, woff, G: int, interpret: bool):
    """One part's ``[G, wr, C]`` x values: its pairs' windows gathered
    from the table, then the lane pick (:func:`kernel_operands` layout)."""
    wr, C, Gp = woff.shape
    W = rows.shape[1]
    bg = pick_block(G)
    nG = Gp // bg
    win = jnp.take(rows, wbase, axis=0, mode="clip")    # [wr·Gp, W]
    out = pl.pallas_call(
        _pick_kernel,
        grid=(wr, nG),
        # block indices are built from the grid indices, so they stay
        # 32-bit under jax_enable_x64 (a literal 0 would be int64)
        in_specs=[pl.BlockSpec((bg, W), lambda j, g: (j * nG + g, g * 0)),
                  pl.BlockSpec((1, C, bg), lambda j, g: (j, g * 0, g))],
        out_specs=pl.BlockSpec((1, C, bg), lambda j, g: (j, g * 0, g)),
        out_shape=jax.ShapeDtypeStruct((wr, C, Gp), rows.dtype),
        name=PICK_KERNEL, interpret=interpret)(win, woff)
    return jnp.transpose(out[:, :, :G], (2, 0, 1))


def window_gather(xc, win, W: int, groups, *, interpret: bool):
    """``x[cols]`` of every part, one ``[G, wr, C]`` array per part (G
    from ``groups``), equal to the scalar gather's on every lane a
    product depends on, from the plan's ``win = (wres, ((wbase, woff),
    ...))`` (:func:`build_window`): one table for all parts
    (:func:`window_table`), then per part one row gather of its pairs'
    windows and the lane pick as a Mosaic kernel (run by the Pallas
    interpreter where ``interpret``)."""
    wres, ops = win
    rows = window_table(xc, wres, W, ops[0][1].shape[1])
    return [_pick(rows, wbase, woff, G, interpret)
            for (wbase, woff), G in zip(ops, groups)]
