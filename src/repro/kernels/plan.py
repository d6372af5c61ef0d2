"""SpMVPlan execution engine: cached plans, single-dispatch SpMV (DESIGN.md
§2.4, §10).

The paper's speedups live or die on SpMV being launch- and memory-lean; the
per-call path used to re-run host-side band planning, re-trace the kernels,
and issue one full-length σ-scatter per width bucket on every matvec. This
module moves every host-side decision out of the hot path:

* :func:`get_plan` builds a :class:`SpMVPlan` once per matrix — band-window
  feasibility, per-bucket tile parameters ``(sb, wb)``, half-window ``hw``,
  and kernel-variant selection — and caches it keyed on
  ``(mat token, sb, wb, hw, policy, interpret, decode-cache mode)``.
  Repeated matvecs (CG/GMRES inner loops, serving ticks) hit the cache and
  the plan's jitted dispatch function: zero host planning, zero re-tracing.
* The epilogue is fused: stored-row outputs get ONE σ-permutation step —
  for concrete plans a *gather* by the plan-precomputed inverse permutation
  (XLA CPU scatters are serial; the gather is ~100× cheaper).
  ``permuted=True`` skips it entirely (see ``cg.jacobi_pcg_stored``).
* For the ``'jnp'`` variant the plan's decode cache comes in three modes
  (``REPRO_PLAN_CURSOR_CACHE`` = ``checkpoint`` | ``full`` | ``0``):

  - ``checkpoint`` (default, DESIGN.md §10) — the **fused ragged stream**:
    all width buckets are repacked once at build time into one
    ``uint32[R, wr]`` word-stream operand (each row = one ``wr``-word run
    of a single stored row) plus ONE int32 **cursor checkpoint per row**
    — the column cursor before the row's first word. Each dispatch is one
    unpack → in-register prefix-sum from the checkpoint → one clip-mode
    gather → one segmented reduction over the per-segment ``(S, C, runs)``
    metadata. No per-word cursor stream (the paper's β is restored: the
    stream is the packed words themselves + 4/wr bytes of checkpoint per
    word), no per-bucket Python loop, no ``concatenate`` epilogue over
    bucket intermediates.
  - ``full`` — the PR-1 cursor cache: column indices decoded at build time,
    one extra int32 per stored word (≈ pack-sized) streamed per matvec.
  - ``0`` — no cache; runtime scan decode (``core.packsell``).

* For the Pallas variants ``checkpoint`` mode builds per-bucket **width
  -block checkpoints** ``int32[S, nw, C]`` (cursor at the start of each
  ``wb``-word grid block): the kernels seed the cursor from the checkpoint
  ref instead of carrying it across width blocks in VMEM scratch, making
  the width dimension of the grid parallel instead of a sequential carry
  chain (``packsell_spmv.py``).
* Variant selection is explicit and logged (:attr:`SpMVPlan.policy`):

  - ``'jnp'``   — the fused-stream / scan-decode XLA path: the ``auto``
    choice on every backend. On a TPU no Pallas body compiles
    (:data:`PALLAS_REFUSAL`); elsewhere the Pallas kernels only run in
    interpret mode.
  - ``'fused'`` — the fused-stream Pallas kernel
    (``packsell_spmv.packsell_spmv_fused``): ONE kernel over the whole
    repacked ``uint32[G, wr, C]`` word stream + ``int32[G, C]``
    checkpoints, grid parallel over group × word-run tiles.
  - ``'band'``  — band-windowed per-bucket Pallas kernel (bounded VMEM;
    RCM/banded regime),
  - ``'full'``  — full-x-in-VMEM per-bucket Pallas kernel.

  The automatic choice can be overridden per call (``force=``) or globally
  via the ``REPRO_SPMV_POLICY`` env var (``auto|fused|full|band|jnp``). The
  Pallas variants are interpret-mode only: forcing one with
  ``interpret=False`` raises at plan build, quoting Mosaic's refusal.
"""
from __future__ import annotations

import dataclasses
import itertools
import os
import weakref
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import codecs as cd
from repro.core import packsell as pk
from repro.core.packsell import PackSELLMatrix
from repro.observe import metrics as _obs
from . import packsell_spmv as _pk
from . import xgather as _xg

_DEF_HW = 4096              # default half-window (elements, multiple of 128)
_FULL_X_LIMIT = int(os.environ.get("REPRO_FULL_X_LIMIT", 2_000_000))

_POLICIES = ("auto", "full", "band", "jnp", "fused")
_PALLAS_VARIANTS = ("full", "band", "fused")

#: Why no Pallas variant runs compiled: Mosaic, the Pallas TPU lowering of
#: the installed JAX (0.9.0), refuses every PackSELL kernel body.
#: ``tests/test_tpu_compile.py`` compiles them for a described v5e and pins
#: these refusals.
PALLAS_REFUSAL = (
    "Mosaic refuses every PackSELL Pallas body: the fused kernels with "
    "'Unimplemented primitive in Pallas TPU lowering for KernelType.TC: "
    "dynamic_slice' (a per-word slice of the loaded word tile) and, when "
    "the tile is read from the ref, 'Only 2D gather is supported' (the 1-D "
    "jnp.take over the whole VMEM-resident x); the bucket kernels on block "
    "shapes (lane dimension C < 128, rank-1 blocks) and, at C = 128, on the "
    "same dynamic_slice")
_CACHE_MODES = ("checkpoint", "full", "0")


class LRUDict(dict):
    """A dict with LRU eviction, capacity read from an env var at insert
    time (so long-running processes can be re-tuned and tests can shrink
    it). Backs the per-plan jit-function caches: eviction only drops a
    compiled executable or the cached operand dict — both rebuild on the
    next call, bit-identically (the computation graph is a pure function
    of the plan's static fields)."""

    def __init__(self, env: str = "REPRO_JIT_CACHE_CAP", cap: int = 64):
        super().__init__()
        self._env = env
        self._default_cap = cap

    def _cap(self) -> int:
        try:
            return int(os.environ.get(self._env, self._default_cap))
        except ValueError:
            return self._default_cap

    def _touch(self, key) -> None:
        val = super().pop(key)
        super().__setitem__(key, val)       # move to MRU position

    def __getitem__(self, key):
        val = super().__getitem__(key)
        self._touch(key)
        return val

    def get(self, key, default=None):
        if key not in self:
            return default
        return self[key]

    def __setitem__(self, key, value) -> None:
        if key in self:
            super().pop(key)
        super().__setitem__(key, value)
        cap = max(self._cap(), 1)
        while len(self) > cap:
            super().pop(next(iter(self)))   # evict LRU
            _obs.inc("jit_cache.evict", cache=self._env)

    @classmethod
    def default_cap(cls) -> int:
        try:
            return int(os.environ.get("REPRO_JIT_CACHE_CAP", 64))
        except ValueError:
            return 64

#: candidate checkpoint row widths (words between checkpoints), largest
#: first. Power-of-two so pow2 bucket widths >= wr need no run padding.
_CKPT_WIDTHS = (128, 64, 32, 16, 8)


def _env_policy() -> str:
    pol = os.environ.get("REPRO_SPMV_POLICY", "auto").lower()
    if pol not in _POLICIES:
        raise ValueError(f"REPRO_SPMV_POLICY={pol!r} not in {_POLICIES}")
    return pol


def _env_cache_mode() -> str:
    raw = os.environ.get("REPRO_PLAN_CURSOR_CACHE", "checkpoint").lower()
    if raw in ("1", "checkpoint"):
        return "checkpoint"          # "1" kept for PR-1 compatibility
    if raw == "full":
        return "full"
    if raw in ("0", "off", "none"):
        return "0"
    raise ValueError(
        f"REPRO_PLAN_CURSOR_CACHE={raw!r} not in {_CACHE_MODES}")


def _interpret_default() -> bool:
    """Pallas kernels run in interpret mode off a TPU. Plans record the
    flag (:attr:`SpMVPlan.interpret`), so a run can check it."""
    return jax.default_backend() != "tpu"


def _is_traced(mat: PackSELLMatrix) -> bool:
    leaves = jax.tree_util.tree_leaves(
        (mat.packs, mat.d0s, mat.outrows, mat.maxcols))
    return any(isinstance(leaf, jax.core.Tracer) for leaf in leaves)


# ---------------------------------------------------------------------------
# Band-window planning (host-side, per bucket)
# ---------------------------------------------------------------------------


def bucket_band_windows(d0, maxcol, sb: int, hw: int):
    """Per-slice-block window ids (half-window units) for one bucket, or
    None when some slice-block's column span exceeds the 2*hw window."""
    d0 = np.asarray(d0)
    mc = np.asarray(maxcol)
    S = len(d0)
    s_pad = -S % sb
    if s_pad:
        d0 = np.concatenate([d0, np.full(s_pad, d0[-1] if S else 0, np.int32)])
        mc = np.concatenate([mc, np.full(s_pad, mc[-1] if S else 0, np.int32)])
    d0b = d0.reshape(-1, sb).min(axis=1)
    mcb = mc.reshape(-1, sb).max(axis=1)
    win = d0b // hw
    if np.any(mcb - win * hw >= 2 * hw):
        return None
    return win.astype(np.int32)


def band_plan(mat: PackSELLMatrix, sb: int, hw: int):
    """Host-side: per-bucket window ids if the band kernel is feasible for
    every slice-block, else None.

    Feasibility needs column locality *within each sb-slice block*; width
    bucketing can interleave distant slices, so banded matrices should be
    built with ``bucket_strategy='uniform'`` (contiguous slices) when the
    band kernel is desired — cheap in the low-RSD regime the paper targets.
    """
    wins = []
    for d0, maxcol in zip(mat.d0s, mat.maxcols):
        win = bucket_band_windows(d0, maxcol, sb, hw)
        if win is None:
            return None
        wins.append(win)
    return wins


# ---------------------------------------------------------------------------
# Host-side delta prefix sums (checkpoint + cursor-cache builders)
# ---------------------------------------------------------------------------


def _bucket_cursor_prefix(pack, d0, codec, D):
    """Exact int64 cursor BEFORE each word of one bucket: ``cum0[s, j, c]``
    = column cursor of stored row (s, c) before consuming word j
    (``cum0[:, 0, :]`` = d0). Shape [S, w+1, C]; entry ``w`` is the final
    cursor."""
    words = np.asarray(pack)
    S, w, C = words.shape
    _, d, _ = cd.unpack_words_np(words.reshape(-1), codec, D)
    cum = np.cumsum(d.reshape(S, w, C).astype(np.int64), axis=1)
    zero = np.zeros((S, 1, C), np.int64)
    return np.asarray(d0)[:, None, None].astype(np.int64) + \
        np.concatenate([zero, cum], axis=1)


# ---------------------------------------------------------------------------
# Cursor-cached decode (jnp variant, mode='full' — the PR-1 layout)
# ---------------------------------------------------------------------------


def _sum_words(p):
    """``p [S, w, C]`` summed over w in a fixed order: explicit add chains
    over blocks of at most 32 words, the block sums chained the same way.
    XLA takes a reduce's order from its operand's layout, which the form
    of the x gather changes; the chain makes the windowed and the scalar
    gather give the same bits."""
    while p.shape[1] != 1:
        S, w, C = p.shape
        if w == 0:
            return jnp.zeros((S, C), p.dtype)
        k = min(w, 32)
        q = jnp.pad(p, ((0, 0), (0, -w % k), (0, 0))).reshape(S, -1, k, C)
        p = q[:, :, 0]
        for j in range(1, k):
            p = p + q[:, :, j]
    return p[:, 0]


def _cursor_spmv(pack, cols, xc, codec, D, xv=None):
    """One bucket via the full cursor cache: value unpack + one x gather
    (``xv``, the windowed gather's values, where the plan has them) +
    one ordered reduction (:func:`_sum_words`) — no runtime cumsum."""
    v, _ = cd.unpack_words_jnp(pack, codec, D)
    if xv is None:
        xv = _take_x(xc, cols)
    return _sum_words(v.astype(jnp.float32) * xv)


def _cursor_spmm(pack, cols, xc, codec, D):
    """Multi-RHS cursor-cached bucket; width-chunked to bound the
    [S, chunk, C, nb] gather intermediate."""
    S, w, C = pack.shape
    nb = xc.shape[1]
    chunk = pk._SCAN_CHUNK
    v, _ = cd.unpack_words_jnp(pack, codec, D)
    acc = jnp.zeros((S, C, nb), jnp.float32)
    for j0 in range(0, w, chunk):
        vc = v[:, j0:j0 + chunk, :].astype(jnp.float32)
        cc = cols[:, j0:j0 + chunk, :]
        with _obs.span("packsell.x_gather"):
            xv = jnp.take(xc, cc.reshape(-1), axis=0,
                          mode="clip").reshape(cc.shape + (nb,))
        acc = acc + jnp.sum(vc[..., None] * xv, axis=1)
    return acc


def _build_cursor_cache(mat: PackSELLMatrix):
    """Decode every bucket's column cursors once (host-side numpy): the
    prefix-sum of word deltas, clamped to [0, m-1] exactly as the runtime
    decode would. Host arrays: the plan build puts them on the device."""
    mlim = max(mat.m - 1, 0)
    cols = []
    for pack, d0 in zip(mat.packs, mat.d0s):
        cum0 = _bucket_cursor_prefix(pack, d0, mat.codec, mat.D)
        cols.append(np.minimum(cum0[:, 1:, :], mlim).astype(np.int32))
    return tuple(cols)


# ---------------------------------------------------------------------------
# Fused ragged stream + compact cursor checkpoints (mode='checkpoint')
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class FusedSegment:
    """One width bucket's span inside the fused stream, laid out
    LEVEL-major over run-count-sorted slices.

    The plan re-orders the bucket's slices by per-slice *content* width
    (descending run count, stable) and trims every all-padding trailing
    run, so level k = run k of the first ``levels[k]`` sorted slices — a
    shrinking contiguous prefix. The segment's reduction is an unrolled
    chain of zero-padded aligned adds (no reshape, no reduce HLO, no
    scatter), and — because bucket padding concentrates in trailing runs
    of the narrower slices — the stream often ends up SMALLER than the
    bucketed packs. The slice re-order is baked into the plan's
    ``outrow_cat``/inverse permutation, so outputs land exactly where the
    epilogue expects them."""

    g0: int
    S: int
    C: int
    levels: tuple            # level k covers sorted slices [0, levels[k])

    @property
    def groups(self) -> int:
        return int(sum(self.levels))

    @property
    def stored(self) -> int:
        return self.S * self.C


@dataclasses.dataclass(frozen=True)
class FusedLayout:
    """Static shape of the fused ragged word stream (device arrays:
    ``words uint32[groups, wr, C]`` + ``ckpt int32[groups, C]``).

    The lane axis C stays minor — the same VREG-friendly orientation as
    the bucketed packs, so every elementwise op and the run-axis
    accumulation vectorize across lanes; a group is a pure reshape of
    ``pack[s, k*wr:(k+1)*wr, :]``, so building the stream is a width-pad
    + reshape, never a transpose.

    ``encoding`` names how each 32-bit stream word carries its
    (value, run-local column offset) pair — the offsets are the word
    deltas **prefix-summed at build time and re-based to the group's
    checkpoint**, so the runtime decode is one add per word, no scan:

    * ``'f16'``     — fp16 payload in the top 16 bits, offset in the low
      16 (requires every group's column span < 2^16).
    * ``'top16'``   — top-16-of-fp32 payload (bf16; E8MY with V <= 16),
      offset in the low 16.
    * ``'fixed16'`` — fixed-point payload in the top 16 bits with the
      static dequant ``scale``, offset in the low 16.
    * ``'words'``   — canonical pack words with the delta field rewritten
      to the re-based offset (any codec; offsets must fit the D-bit
      field of flag=1 words).
    """

    wr: int                  # words per group per lane == ckpt granularity
    groups: int
    C: int
    words_exact: int         # bucketed words before run padding
    segments: tuple          # of FusedSegment, in bucket order
    encoding: str = "words"
    scale: float = 0.0       # fixed16 dequant scale

    @property
    def pad_words(self) -> int:
        return self.groups * self.wr * self.C - self.words_exact

    @property
    def checkpoint_bytes(self) -> int:
        return 4 * self.groups * self.C

    @property
    def stream_bytes(self) -> int:
        return 4 * self.groups * self.wr * self.C


#: cost-model constants for the checkpoint-width choice: a streamed word
#: costs ~3 passes (decode + x-gather + fma), a level add re-reads +
#: rewrites the [S_k, C] accumulator (~3 passes per element), and every
#: level is one more XLA op on the dispatch path (~tens of µs ≈ 40k
#: element-passes on the CPU backend). Fit against the small benchmark
#: suite; only the argmin matters, not the absolute scale.
_STREAM_PASSES = 3
_LEVEL_ADD_PASSES = 3
_LEVEL_OP_ELEMS = 40_000


def _pick_ckpt_width(widths, total: int) -> int:
    """Checkpoint width minimizing the modeled per-matvec cost, subject
    to the decode cache shrinking >= ``min(_CKPT_WIDTHS)``× vs the full
    cursor cache. ``widths`` is the list of (per-slice content widths, C)
    pairs per bucket; after all-pad-run trimming the stream holds
    ``ceil(width/wr)*wr`` words per slice. Small ``wr`` trims more
    padding but deepens the level chains of wide buckets (accumulator
    re-streaming + one op per level), so the model charges both; ties
    prefer the larger width (fewer checkpoints)."""
    floor = _CKPT_WIDTHS[-1]
    best = None                      # (ineligible, cost, -wr)
    for wr in _CKPT_WIDTHS:
        streamed = groups = levels = slices = 0
        for w, C in widths:
            runs = -(-np.maximum(w, 1) // wr)
            streamed += int(runs.sum()) * wr * C
            groups += int(runs.sum())
            levels += int(runs.max(initial=1)) - 1
            slices += len(w)
            last_C = C
        cbytes = groups * (last_C if widths else 1)
        shrink = total / cbytes if cbytes else float("inf")
        cost = _STREAM_PASSES * streamed \
            + _LEVEL_ADD_PASSES * (groups - slices) * (last_C if widths
                                                       else 1) \
            + _LEVEL_OP_ELEMS * levels
        key = (shrink < floor, cost, -wr)
        if best is None or key < best[0]:
            best = (key, wr)
    return best[1]


def _split16_encoding(mat: PackSELLMatrix):
    """The 16/16 split encoding for this matrix's codec, or None.

    Valid when the word's value payload lives entirely in the top 16 bits
    (fp16/bf16 embed at any D; E8MY and fixed-point once V = 31-D <= 16),
    so the plan stream can carry (payload16 | offset16) and the decode is
    two fixed shifts — no flag arithmetic, no variable shifts."""
    name, D = mat.codec_name, mat.D
    if name == "fp16":
        return "f16", 0.0
    if name == "bf16":
        return "top16", 0.0
    if name == "e8m" and cd.vbits_for(D) <= 16:
        return "top16", 0.0
    if name.startswith("fixed") and cd.vbits_for(D) <= 16:
        frac = int(name[len("fixed"):])
        return "fixed16", float(2.0 ** -(frac + D - 15))
    return None


def _stream_encoding(split, locals_max: int, flag1_max: int, D: int):
    """The fused stream's ``(encoding, scale)`` for its largest offset
    ``locals_max`` (``flag1_max`` among value words), or None when no
    compact encoding holds them; larger offsets never make one fit, so
    the builder stops at the first bucket that overflows."""
    if split is not None and locals_max < (1 << 16):
        return split
    if flag1_max < (1 << D) and locals_max < (1 << 31):
        return "words", 0.0
    return None


def _build_fused_stream(mat: PackSELLMatrix, *, trim: bool = True,
                        wr: int | None = None):
    """Repack the bucketed words into the fused ragged-group layout, once,
    host-side (DESIGN.md §10.1). Returns ``((words3d, ckpt), layout,
    orders)``, the stream as host arrays — ``orders`` is the per-bucket
    slice permutation the caller must bake into ``outrow_cat`` — or
    ``(None, None, None)`` when no encoding fits (a group's column span
    overflows every offset field — the caller falls back to the full
    cursor cache).

    Each bucket's slices are sorted by content width (descending run
    count, stable), their word runs padded to a multiple of ``wr`` with
    ``PAD_WORD`` (flag=0, delta=0: contributes nothing) and carved into
    ``wr``-word groups laid out LEVEL-major: level k = run k of the
    sorted slices that still have one — all-padding trailing runs are
    trimmed away, which is where SELL bucket padding lives, so the
    stream is usually *smaller* than the bucketed packs. ``ckpt[g, c]``
    is the exact column cursor of stored row (slice-of(g), c) before the
    group's first word, and every word's delta is replaced by its
    **build-time prefix sum re-based to that checkpoint**, so the
    runtime column decode is ONE add per word — no scan, no carry, no
    per-word cursor stream.

    ``trim=False`` keeps the identity slice order and the full
    shape-derived run count per slice (every level = all S slices): the
    layout then depends only on the bucket SHAPES, which SPMD consumers
    (the distributed stacker) need uniform across shards. ``wr=`` pins
    the checkpoint width instead of the modeled pick — the autotune
    sweep's third axis (:meth:`SpMVPlan.retile` triples).
    """
    C, D = mat.C, mat.D
    dmask = np.uint32(cd.delta_mask(D))
    total = sum(int(np.prod(p.shape)) for p in mat.packs)
    used_w = []
    for pack in mat.packs:
        words = np.asarray(pack)
        S, w, C = words.shape
        if trim:
            nz = (words != pk.PAD_WORD).any(axis=2)        # [S, w]
            used = np.where(nz.any(axis=1),
                            w - np.argmax(nz[:, ::-1], axis=1), 1)
        else:
            used = np.full(S, w, np.int64)
        used_w.append((used.astype(np.int64), C))
    wr = _pick_ckpt_width(used_w, total) if wr is None else max(int(wr), 1)

    per_bucket, segs, orders = [], [], []
    g0 = 0
    locals_max = 0
    flag1_max = 0
    split = _split16_encoding(mat)
    for (used, _), pack, d0 in zip(used_w, mat.packs, mat.d0s):
        words = np.asarray(pack)
        S, w, C = words.shape
        runs_s = -(-np.maximum(used, 1) // wr)             # >= 1 per slice
        order = np.argsort(-runs_s, kind="stable").astype(np.int64)
        runs_sorted = runs_s[order]
        maxr = int(runs_sorted[0]) if S else 1
        levels = tuple(int((runs_sorted > k).sum()) for k in range(maxr))
        wpad = maxr * wr
        cum0 = _bucket_cursor_prefix(pack, d0, mat.codec, D)[order]
        wp = np.full((S, wpad, C), pk.PAD_WORD, np.uint32)
        wk = min(w, wpad)           # trimming can shrink below w
        wp[:, :wk, :] = words[order][:, :wk, :]
        ck = cum0[:, ::wr, :][:, :maxr, :]                 # [S, maxr, C]
        # inclusive cursor per word, padding words frozen at the last real
        # cursor, re-based to the group checkpoint
        cum = np.concatenate(
            [cum0[:, 1:, :],
             np.broadcast_to(cum0[:, -1:, :],
                             (S, max(wpad - w, 0), C))], axis=1)[:, :wpad]
        local = (cum.reshape(S, maxr, wr, C)
                 - ck[:, :, None, :]).reshape(S, wpad, C)
        flag = wp & np.uint32(1)
        # only the KEPT groups constrain the encoding
        keep = np.zeros((S, maxr), bool)
        for k, Sk in enumerate(levels):
            keep[:Sk, k] = True
        keepw = np.repeat(keep, wr, axis=1)[:, :, None]
        lk = np.where(keepw, local, 0)
        locals_max = max(locals_max, int(lk.max(initial=0)))
        f1 = lk[(flag == 1) & keepw]
        flag1_max = max(flag1_max, int(f1.max(initial=0)))
        enc = _stream_encoding(split, locals_max, flag1_max, D)
        if enc is None:
            return None, None, None     # span overflow: no compact encoding
        per_bucket.append((wp, flag, local, ck, S, maxr, levels))
        segs.append(FusedSegment(g0=g0, S=S, C=C, levels=levels))
        orders.append(order)
        g0 += int(sum(levels))

    encoding, scale = _stream_encoding(split, locals_max, flag1_max, D)

    blk_w, blk_c = [], []
    for wp, flag, local, ck, S, maxr, levels in per_bucket:
        lu = np.minimum(local, (1 << 16) - 1 if encoding != "words"
                        else (1 << 31) - 1).astype(np.uint32)
        if encoding == "words":
            payload = wp & ~dmask
            w1 = payload | (lu << np.uint32(1)) | np.uint32(1)
            w0 = lu << np.uint32(1)
            nw = np.where(flag == 1, w1, w0)
        else:
            # value payload is top-16-aligned: keep it, splice the offset
            payload16 = np.where(flag == 1, wp & ~dmask, np.uint32(0))
            nw = (payload16 & np.uint32(0xFFFF0000)) | lu
        C_b = nw.shape[-1]
        nw4 = nw.reshape(S, maxr, wr, C_b)
        ck3 = ck
        for k, Sk in enumerate(levels):
            blk_w.append(nw4[:Sk, k])
            blk_c.append(ck3[:Sk, k])
    words3d = (np.concatenate(blk_w) if blk_w
               else np.zeros((0, wr, C), np.uint32))
    ckpt = (np.concatenate(blk_c) if blk_c
            else np.zeros((0, C), np.int64))
    layout = FusedLayout(
        wr=wr, groups=g0, C=C, words_exact=total,
        segments=tuple(segs), encoding=encoding, scale=scale)
    return (words3d, ckpt.astype(np.int32)), layout, orders


def _fused_decode(w, codec, D, layout: FusedLayout):
    """(value f32, run-local column offset i32) for a stream slice.
    Delegates to :func:`packsell_spmv.fused_decode_word` — the single
    decode definition shared with the fused Pallas kernels, so the
    'jnp' and 'fused' variants stay bit-compatible by construction."""
    return _pk.fused_decode_word(w, codec, D, layout.encoding,
                                 layout.scale)


def _fused_tail2(part, layout: FusedLayout):
    """Segmented reduction over group partials: [groups, C(, nb)] →
    [total_slices, C(, nb)] in sorted-slice-major stored order. The
    level-major layout makes each segment's reduction an unrolled chain
    of zero-padded aligned adds over shrinking slice prefixes (static
    slices; no reshape, no reduce HLO, no scatter) — and when every
    segment is single-level the partials ARE the result, copy-free."""
    if not layout.segments or all(len(seg.levels) == 1
                                  for seg in layout.segments):
        return part
    pad_tail = ((0, 0),) * (part.ndim - 1)
    outs = []
    for seg in layout.segments:
        t = part[seg.g0:seg.g0 + seg.levels[0]]
        off = seg.levels[0]
        for Sk in seg.levels[1:]:
            lk = part[seg.g0 + off:seg.g0 + off + Sk]
            if Sk < seg.S:
                lk = jnp.pad(lk, ((0, seg.S - Sk),) + pad_tail)
            t = t + lk
            off += Sk
        outs.append(t)
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs)


def _fused_tail(part, layout: FusedLayout):
    """[groups, C(, nb)] → flat [total_stored(, nb)] in ``outrow_cat``
    order (the ``permuted=True`` contract). The flattening reshape is a
    real XLA copy on CPU, so the un-permuted epilogue avoids this path
    and gathers 2-D (:func:`_fused_unpermute2`)."""
    tail = tuple(part.shape[2:])
    if not layout.segments:
        return jnp.zeros((0,) + tail, part.dtype)
    return _fused_tail2(part, layout).reshape((-1,) + tail)


def _fused_unpermute2(t2, inv2):
    """y[r] = t2[slice(r), lane(r)] — the σ-unpermutation applied
    directly to the 2-D slice-major tail, skipping the flatten copy AND
    the separate 1-D gather (one gather, unique in-bounds indices)."""
    return t2.at[inv2[:, 0], inv2[:, 1]].get(mode="clip",
                                             unique_indices=True)


def _fused_part_spmv(words3d, ckpt, xc, codec, D, layout: FusedLayout,
                     xv=None):
    """The fused single-pass SpMV body (group partials [G, C]): one
    decode over the whole stream, one checkpoint add, one x gather
    (``xv``, the windowed gather's values, where the plan has them), an
    unrolled accumulate over the group-width axis (an explicit add chain
    — XLA fuses it into one pass where its reduce HLO would not)."""
    wr = words3d.shape[1]
    v, local = _fused_decode(words3d, codec, D, layout)
    if xv is None:
        xv = _take_x(xc, ckpt[:, None, :] + local)
    p = v * xv
    acc = p[:, 0, :]
    for j in range(1, wr):
        acc = acc + p[:, j, :]
    return acc


def _fused_part_spmm(words3d, ckpt, xc, codec, D, layout: FusedLayout):
    """Multi-RHS fused pass (group partials [G, C, nb]): per word
    position, decode + gather + FMA on [G, C, nb] slices (bounds the
    gather intermediate the way the cursor path's width chunking did,
    with the same unrolled accumulation)."""
    G, wr, C = words3d.shape
    nb = xc.shape[1]
    acc = None
    for j in range(wr):
        v, local = _fused_decode(words3d[:, j, :], codec, D, layout)
        cols = ckpt + local
        with _obs.span("packsell.x_gather"):
            xv = jnp.take(xc, cols.reshape(-1), axis=0,
                          mode="clip").reshape(G, C, nb)
        t = v[..., None] * xv
        acc = t if acc is None else acc + t
    if acc is None:
        acc = jnp.zeros((G, C, nb), jnp.float32)
    return acc


# ---------------------------------------------------------------------------
# Windowed x gather (DESIGN.md §10.5; host build and kernel in xgather.py)
# ---------------------------------------------------------------------------


def _fused_gather_columns(words3d, ckpt, mat: PackSELLMatrix,
                          layout: FusedLayout):
    """Host ``(cols, free)`` of the fused stream, both ``[G, wr, C]``:
    the column the scalar gather reads for every word (checkpoint +
    offset, clip-clamped to x as ``jnp.take(mode='clip')`` clamps it)
    and whether the word's value is ±0, so its product is ±0 whatever
    x entry it reads (padding, dummies)."""
    w = np.asarray(words3d)
    if layout.encoding == "words":
        v, local, _ = cd.unpack_words_np(w, mat.codec, mat.D)
        free = v == 0
    else:
        local = w & np.uint32(0xFFFF)
        top = (np.uint32(0xFFFF0000) if layout.encoding == "fixed16"
               else np.uint32(0x7FFF0000))       # ±0 in f16/top16
        free = (w & top) == 0
    cols = np.asarray(ckpt)[:, None, :].astype(np.int64) + local
    return np.clip(cols, 0, max(mat.m - 1, 0)).astype(np.int32), free


def _pin_empty_rows(free, layout: Optional[FusedLayout]):
    """``free`` with the words of stored rows that hold no real word put
    back: such a row's output is a sum of ±0 products, whose sign follows
    the x entries read, so its words read exactly what the scalar gather
    reads. ``layout`` maps the fused stream's groups to stored rows
    (level-major segments); None is one cursor-cache bucket ``[S, w,
    C]``, a slice per stored row."""
    lane_free = free.all(axis=1)                       # [G, C]
    if layout is None:
        return free & ~lane_free[:, None, :]
    pinned = np.zeros_like(lane_free)
    for seg in layout.segments:
        empty = np.ones((seg.S, seg.C), bool)
        g = seg.g0
        for Sk in seg.levels:
            empty[:Sk] &= lane_free[g:g + Sk]
            g += Sk
        g = seg.g0
        for Sk in seg.levels:
            pinned[g:g + Sk] = empty[:Sk]
            g += Sk
    return free & ~pinned[:, None, :]


def _plan_window(mat: PackSELLMatrix, fused, layout, cols):
    """:func:`xgather.build_window` over the plan's x-gather columns — the
    fused stream's, or each bucket's cursor cache (host arrays)."""
    with _obs.host_span("packsell.plan_build.window"):
        if fused is not None:
            c, free = _fused_gather_columns(fused[0], fused[1], mat, layout)
            parts = [(c, _pin_empty_rows(free, layout))]
        elif cols is not None:
            parts = []
            for pack, c in zip(mat.packs, cols):
                words = np.asarray(pack)
                v, _, _ = cd.unpack_words_np(words.reshape(-1), mat.codec,
                                             mat.D)
                free = (v == 0).reshape(words.shape)
                parts.append((np.asarray(c), _pin_empty_rows(free, None)))
        else:
            return 0, None
        return _xg.build_window(parts, mat.m)


def _take_x(xc, cols):
    """``xc[cols]`` (clip-clamped): the scalar x gather of a decode
    body."""
    with _obs.span("packsell.x_gather"):
        return jnp.take(xc, cols.reshape(-1), axis=0,
                        mode="clip").reshape(cols.shape)


def _build_block_checkpoints(mat: PackSELLMatrix, tiles):
    """Per-bucket ``int32[S, nw, C]`` width-block checkpoints for the
    Pallas kernels: the cursor before word ``wi * wb`` of each stored row.
    Replaces the kernels' d0-seeded sequential VMEM cursor carry
    (``packsell_spmv.py``); recomputed on :meth:`SpMVPlan.retile` because
    the granularity is the width-block size ``wb``. Host arrays."""
    out = []
    for (sb, wb), pack, d0 in zip(tiles, mat.packs, mat.d0s):
        words = np.asarray(pack)
        S, w, C = words.shape
        nw = -(-w // wb)
        cum0 = _bucket_cursor_prefix(pack, d0, mat.codec, mat.D)
        ck = cum0[:, ::wb, :][:, :nw, :]
        out.append(ck.astype(np.int32))
    return tuple(out)


def stored_permute(v, outrow_cat, n: int):
    """Original-row-order → stored-row order (σ-padding slots become 0).
    Operand-explicit so jitted callers (the fused solver step in
    ``solvers/cg.py``) can pass the plan buffers as arguments instead of
    closure constants."""
    val = jnp.take(v, outrow_cat, axis=0, mode="clip")
    mask = (outrow_cat < n).reshape((-1,) + (1,) * (v.ndim - 1))
    return jnp.where(mask, val, 0).astype(v.dtype)


def stored_unpermute(t, inv_cat):
    """Stored-row order → original-row order: the σ-permutation applied
    as a gather by the precomputed inverse map (equals the scatter
    bit-for-bit: each original row has exactly one stored slot, so the
    indices are unique and in-bounds)."""
    return jnp.take(t, inv_cat, axis=0, mode="clip", unique_indices=True)


def _build_inverse_perm(mat: PackSELLMatrix, outrow_cat):
    """inv[r] = stored slot of original row r (each row has exactly one),
    turning the σ-scatter epilogue into a gather. A host array."""
    outrow_np = np.asarray(outrow_cat)
    valid = outrow_np < mat.n
    inv = np.zeros(mat.n, np.int32)
    inv[outrow_np[valid]] = np.nonzero(valid)[0].astype(np.int32)
    return inv


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SpMVPlan:
    """Everything host-side the hot path would otherwise recompute.

    Static decisions (variant, tiles, windows, decode-cache layout, the
    concatenated σ-scatter map) are fixed at build time; :meth:`spmv` /
    :meth:`spmm` dispatch straight into a cached jitted executable.
    """

    variant: str                      # 'fused' | 'band' | 'full' | 'jnp'
    policy: str                       # human-readable decision log
    hw: int
    interpret: bool
    tiles: tuple                      # per-bucket (sb, wb)
    wins: Optional[tuple]             # per-bucket int32 windows (band only)
    outrow_cat: jnp.ndarray           # int32 [total_stored] fused scatter map
    n: int
    m: int
    total_stored: int
    inv_cat: Optional[jnp.ndarray] = None   # int32 [n] inverse σ-permutation
    inv2_cat: Optional[jnp.ndarray] = None  # int32 [n, 2] (slice, lane) form
    cols: Optional[tuple] = None      # per-bucket int32 [S, w, C] cursor cache
    cache_mode: str = "0"             # 'checkpoint' | 'full' | '0'
    fused: Optional[tuple] = None     # (words2d uint32[R, wr], ckpt int32[R])
    fused_layout: Optional[FusedLayout] = None
    kckpts: Optional[tuple] = None    # per-bucket int32 [S, nw, C] (Pallas)
    window: Optional[tuple] = None    # (wres, ((wbase, woff), ...)), §10.5
    window_width: int = 0             # W of the windowed x gather; 0 = off
    total_words: int = 0              # bucketed words (decode-cache pricing)
    fused_trim: bool = True           # fused layout built with trimming?
    ephemeral: bool = False           # built under tracing: never cached/jitted
    _matref: Optional[weakref.ref] = None
    _fns: dict = dataclasses.field(default_factory=LRUDict)
    _view: Optional[PackSELLMatrix] = None

    # -- σ-permutation helpers (stored-row order <-> original order) -------
    def _unpermute(self, t, inv_cat, outrow_cat):
        if inv_cat is not None:
            return stored_unpermute(t, inv_cat)
        # tracing fallback: ONE drop-mode scatter over the already-fused
        # stored vector (never per bucket); sentinel slots (>= n) drop, and
        # the surviving indices are unique by construction
        shape = (self.n,) + tuple(t.shape[1:])
        return jnp.zeros(shape, t.dtype).at[outrow_cat].set(t, mode="drop")

    def from_stored(self, t: jnp.ndarray) -> jnp.ndarray:
        """Map a stored-row-order vector [total_stored] (or
        [total_stored, nb]) back to original row order [n] ([n, nb])."""
        return self._unpermute(t, self.inv_cat, self.outrow_cat)

    def to_stored(self, v: jnp.ndarray) -> jnp.ndarray:
        """Gather an original-row-order vector into stored-row order;
        σ-padding slots become 0 (they stay 0 through SpMV, so stored-space
        dot products equal original-space ones)."""
        return stored_permute(v, self.outrow_cat, self.n)

    # -- execution ---------------------------------------------------------
    def _device_operands(self) -> dict:
        """Plan-held device buffers, passed as jit *arguments* so XLA never
        constant-folds them into (or duplicates them inside) the
        executable. Cached: the dict is rebuilt only after retile()."""
        dev = self._fns.get("_dev")
        if dev is None:
            dev = {"cols": self.cols, "inv": self.inv_cat,
                   "inv2": self.inv2_cat, "outrow": self.outrow_cat,
                   "fused": self.fused, "kckpt": self.kckpts,
                   "window": self.window}
            self._fns["_dev"] = dev
        return dev

    def _mm_vmem_fallback(self) -> bool:
        """Multi-RHS VMEM-residency guard: the spmm kernels (bucket AND
        fused) hold the whole ``[m, nb]`` x block in VMEM, so past the
        full-x limit the plan routes spmm through an XLA body instead of
        raising (the decision is static — logged once in :meth:`spmm`)."""
        return (self.variant in ("band", "full", "fused")
                and self.m > _FULL_X_LIMIT)

    def _execute(self, mat: PackSELLMatrix, dev: dict, x: jnp.ndarray,
                 permuted: bool) -> jnp.ndarray:
        xc = x.astype(jnp.float32)
        fused = dev.get("fused")
        if fused is not None and self.variant in ("jnp", "fused"):
            lay = self.fused_layout
            if self.variant == "fused":
                with _obs.span("packsell.fused_kernel"):
                    part = _pk.packsell_spmv_fused(
                        fused[0], fused[1], xc,
                        codec_name=mat.codec_name, D=mat.D,
                        encoding=lay.encoding, scale=lay.scale,
                        gb=self.tiles[0][0] if self.tiles else 8,
                        interpret=self.interpret)
            else:
                with _obs.span("packsell.fused_decode"):
                    xv = self._window_gather(xc, dev, [fused[0].shape])
                    part = _fused_part_spmv(
                        fused[0], fused[1], xc, mat.codec, mat.D, lay,
                        xv[0] if xv else None)
            return self._fused_epilogue(part, dev, permuted)
        if self.variant == "fused":
            raise ValueError("fused plan dispatched without its stream "
                             "operand (dev['fused'] is None)")
        with _obs.span("packsell.bucket_decode"):
            t_cat = self._bucket_parts(mat, dev, x, xc, multi_rhs=False)
        if permuted:
            return t_cat
        with _obs.span("packsell.gather_epilogue"):
            return self._unpermute(t_cat, dev.get("inv"), dev["outrow"])

    def _execute_mm(self, mat: PackSELLMatrix, dev: dict, x: jnp.ndarray,
                    permuted: bool) -> jnp.ndarray:
        xc = x.astype(jnp.float32)
        fused = dev.get("fused")
        if fused is not None and self.variant in ("jnp", "fused"):
            lay = self.fused_layout
            if self.variant == "fused" and not self._mm_vmem_fallback():
                with _obs.span("packsell.fused_kernel"):
                    part = _pk.packsell_spmm_fused(
                        fused[0], fused[1], xc,
                        codec_name=mat.codec_name, D=mat.D,
                        encoding=lay.encoding, scale=lay.scale,
                        gb=self.tiles[0][0] if self.tiles else 8,
                        interpret=self.interpret)
            else:
                # 'jnp', or a fused plan whose x block breaks VMEM
                # residency: same decode, XLA body
                with _obs.span("packsell.fused_decode"):
                    part = _fused_part_spmm(fused[0], fused[1], xc,
                                            mat.codec, mat.D, lay)
            return self._fused_epilogue(part, dev, permuted)
        if self.variant == "fused":
            raise ValueError("fused plan dispatched without its stream "
                             "operand (dev['fused'] is None)")
        with _obs.span("packsell.bucket_decode"):
            t_cat = self._bucket_parts(mat, dev, x, xc, multi_rhs=True)
        if permuted:
            return t_cat
        with _obs.span("packsell.gather_epilogue"):
            return self._unpermute(t_cat, dev.get("inv"), dev["outrow"])

    def _fused_epilogue(self, part, dev: dict, permuted: bool):
        """Reduce group partials to the requested order. Un-permuted
        output gathers 2-D straight off the slice-major tail
        (:func:`_fused_unpermute2`): no flatten copy, one gather."""
        with _obs.span("packsell.gather_epilogue"):
            if permuted:
                return _fused_tail(part, self.fused_layout)
            inv2 = dev.get("inv2")
            if inv2 is not None:
                return _fused_unpermute2(
                    _fused_tail2(part, self.fused_layout), inv2)
            return self._unpermute(_fused_tail(part, self.fused_layout),
                                   dev.get("inv"), dev["outrow"])

    def _bucket_parts(self, mat, dev, x, xc, *, multi_rhs: bool):
        """The per-bucket execution bodies (Pallas variants, the 'full'
        cursor cache, and the tracing scan fallback)."""
        kck = dev.get("kckpt")
        xvs = None
        if not multi_rhs and dev["cols"] is not None:
            xvs = self._window_gather(xc, dev, [p.shape for p in mat.packs])
        parts = []
        for b, (pack, d0) in enumerate(zip(mat.packs, mat.d0s)):
            sb, wb = self.tiles[b]
            ck = None if kck is None else kck[b]
            if multi_rhs:
                if (self.variant in ("band", "full")
                        and not self._mm_vmem_fallback()):
                    # multi-RHS ships the full-x kernel only; a banded plan
                    # falls back to it. Past the VMEM residency limit the
                    # bucket routes to an XLA body below instead.
                    t = _pk.packsell_spmm_bucket(
                        pack, d0, x, codec_name=mat.codec_name, D=mat.D,
                        sb=sb, wb=wb, interpret=self.interpret, ckpt=ck)
                elif dev["cols"] is not None:
                    t = _cursor_spmm(pack, dev["cols"][b], xc, mat.codec,
                                     mat.D)
                else:
                    t = pk._bucket_spmm_scan(
                        pack, d0, xc, mat.codec, mat.D,
                        np.int32(max(mat.m - 1, 0)), jnp.float32)
                parts.append(t.reshape(-1, xc.shape[1]))
                continue
            if self.variant == "band":
                t = _pk.packsell_spmv_band_bucket(
                    pack, d0, jnp.asarray(self.wins[b]), x,
                    codec_name=mat.codec_name, D=mat.D, hw=self.hw,
                    sb=sb, wb=wb, interpret=self.interpret, ckpt=ck)
            elif self.variant == "full":
                t = _pk.packsell_spmv_bucket(
                    pack, d0, x, codec_name=mat.codec_name, D=mat.D,
                    sb=sb, wb=wb, interpret=self.interpret, ckpt=ck)
            elif dev["cols"] is not None:
                t = _cursor_spmv(pack, dev["cols"][b], xc, mat.codec, mat.D,
                                 xvs[b] if xvs else None)
            else:
                t = pk._bucket_spmv_scan(
                    pack, d0, xc, mat.codec, mat.D,
                    np.int32(max(mat.m - 1, 0)), jnp.float32)
            parts.append(t.reshape(-1))
        if not parts:
            shape = (0, xc.shape[1]) if multi_rhs else (0,)
            return jnp.zeros(shape, jnp.float32)
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def _window_gather(self, xc, dev: dict, shapes):
        """x at every word of the parts shaped ``shapes`` (``[G, wr,
        C]``) through the plan's windowed gather
        (:func:`xgather.window_gather`), or None where ``dev`` has no
        window operands (W = 0, stacked shard operands)."""
        win = dev.get("window")
        if win is None:
            return None
        with _obs.span("packsell.x_gather"):
            return _xg.window_gather(xc, win, self.window_width,
                                     [s[0] for s in shapes],
                                     interpret=self.interpret)

    def _dispatch(self, kind: str):
        fn = self._fns.get(kind)
        if fn is None:
            impl = self._execute if kind == "spmv" else self._execute_mm
            fn = jax.jit(impl, static_argnums=(3,))
            self._fns[kind] = fn
        return fn

    def execute_with(self, mat: PackSELLMatrix, dev: dict, x: jnp.ndarray,
                     *, permuted: bool = False,
                     multi_rhs: bool = False) -> jnp.ndarray:
        """Run the plan's execution body with externally supplied device
        operands (``{'cols': tuple|None, 'inv': array|None, 'outrow':
        array, 'fused': (words2d, ckpt)|None, 'kckpt': tuple|None,
        'window': (wres, ((wbase, woff), ...))|None}``; missing keys are
        treated as None, and without ``'window'`` the decode gathers x with one scalar
        gather) inside an existing trace — the shard_map reuse hook.

        The distributed layer builds one concrete plan per shard, stacks
        the per-shard operands along the mesh axis, and calls this inside
        the mapped body with each shard's slice (``DistSpMVPlan``): the
        plan's static decisions (variant, tiles, fused-stream layout) are
        reused across shards while the arrays flow through shard_map
        in_specs.
        """
        impl = self._execute_mm if multi_rhs else self._execute
        return impl(mat, dev, x, permuted)

    def _exec_mat(self, mat: PackSELLMatrix) -> PackSELLMatrix:
        """What the jitted dispatch receives as the matrix argument. The
        fused body reads only the plan's stream operands plus the static
        codec metadata, so a placeholder-leaf view keeps the per-call
        pytree flattening down to a handful of arrays (the distributed
        layer's `_member_view` trick)."""
        if self.fused is None or self.variant not in ("jnp", "fused"):
            return mat
        if self._view is None:
            # numpy placeholders: building the view must never capture a
            # live trace (spmv can be first called inside a solver trace)
            z1 = np.zeros((1,), np.int32)
            self._view = PackSELLMatrix(
                packs=(np.zeros((1, 1, 1), np.uint32),), d0s=(z1,),
                outrows=(z1,), maxcols=(z1,),
                perm=np.zeros((1,), np.uint8),
                n=mat.n, m=mat.m, C=mat.C, sigma=mat.sigma, D=mat.D,
                codec_name=mat.codec_name, k_left=mat.k_left, nnz=mat.nnz,
                n_dummy=mat.n_dummy,
                words_sell_padded=mat.words_sell_padded,
                words_bucketed=mat.words_bucketed)
        return self._view

    def _gauge_counts(self, mat: PackSELLMatrix) -> None:
        """Per-plan gauges, set when the plan is built (and when a retile
        rebuilds its stream), never per call: the 32-bit words the
        executed decode streams per call (the fused stream on the
        checkpoint path, the bucketed packs otherwise), the int32
        cursor-cache or checkpoint words it reads, dummies and nnz; and
        the windowed x gather's width W (0 when off), its window indices
        per call (one per (group, word) pair) and its residual words (the
        indices left to the scalar gather)."""
        dcs = self.decode_cache_stats()
        on_stream = (self.fused is not None
                     and self.variant in ("jnp", "fused"))
        lab = dict(variant=self.variant, codec=mat.codec_name,
                   cache_mode=self.cache_mode)
        _obs.gauge("plan.decode_words",
                   dcs["fused_stream_bytes"] // 4 if on_stream
                   else int(mat.words_bucketed), **lab)
        _obs.gauge("plan.cache_words", dcs["decode_cache_bytes"] // 4,
                   **lab)
        _obs.gauge("plan.dummies", int(mat.n_dummy), **lab)
        _obs.gauge("plan.nnz", int(mat.nnz), **lab)
        if self.window is None:
            pairs = resid = 0
        else:
            shapes = ([self.fused[0].shape] if self.fused is not None
                      else [p.shape for p in mat.packs])
            pairs = sum(int(s[0] * s[1]) for s in shapes)
            resid = int(self.window[0].shape[0])
        _obs.gauge("plan.window_width", int(self.window_width), **lab)
        _obs.gauge("plan.window_pairs", pairs, **lab)
        _obs.gauge("plan.residual_words", resid, **lab)

    def _obs_handles(self, mat: PackSELLMatrix, kind: str):
        """``(span, bump)`` for one recorded dispatch (DESIGN.md §12): the
        ``packsell.dispatch{kind}`` host span factory and the counter bump
        of variant, checkpoint width ``wr``, hot-path stream bytes and
        bytes/nnz. Used only from host entry points with concrete
        operands — never from inside a trace, where a record would freeze
        at trace time. The derived byte figures are per-plan constants,
        computed once and parked in ``_fns`` (cleared by :meth:`retile`,
        so they re-derive)."""
        handles = self._fns.get(("_obs", kind))
        if handles is None:
            dcs = self.decode_cache_stats()
            stream = (dcs["fused_stream_bytes"] or 4 * self.total_words) \
                + dcs["decode_cache_bytes"]
            lab = dict(variant=self.variant, codec=mat.codec_name,
                       cache_mode=self.cache_mode)
            # per-plan constants: gauges set once here, not per call (a
            # registry reset() loses them until the next retile — fine,
            # they describe the plan, not traffic)
            _obs.gauge("spmv.wr", 0 if self.fused_layout is None
                       else int(self.fused_layout.wr), **lab)
            _obs.gauge("spmv.stream_bytes", int(stream), **lab)
            _obs.gauge("spmv.bytes_per_nnz",
                       stream / max(int(mat.nnz), 1), **lab)
            # label sort/stringification paid once per (plan, kind): the
            # steady-state record is one prebuilt two-counter closure
            bump = _obs.counter_bump((
                (_obs.series_key("spmv.dispatch", kind=kind, **lab), 1),
                (_obs.series_key("spmv.nnz", **lab), int(mat.nnz))))
            handles = (_obs.host_span_handle("packsell.dispatch", kind=kind),
                       bump)
            self._fns[("_obs", kind)] = handles
        return handles

    def spmv(self, mat: PackSELLMatrix, x: jnp.ndarray, *,
             permuted: bool = False) -> jnp.ndarray:
        """y = A @ x — one jitted dispatch; ``permuted=True`` returns y in
        stored-row order, skipping the σ-permutation epilogue entirely."""
        if self.ephemeral or _is_traced(mat):
            return self._execute(mat, self._device_operands(), x, permuted)
        if _obs.enabled() and not isinstance(x, jax.core.Tracer):
            span, bump = self._obs_handles(mat, "spmv")
            with span():
                bump()
                return self._dispatch("spmv")(self._exec_mat(mat),
                                              self._device_operands(), x,
                                              permuted)
        return self._dispatch("spmv")(self._exec_mat(mat),
                                      self._device_operands(), x,
                                      permuted)

    def spmm(self, mat: PackSELLMatrix, x: jnp.ndarray, *,
             permuted: bool = False) -> jnp.ndarray:
        """Y = A @ X for X: [m, nb] via the multi-RHS kernel.

        spmm has no banded-window variant: the whole [m, nb] x block must
        be VMEM-resident, so past the full-x limit a band/full plan routes
        to the scan-decode XLA body and a fused plan to the jnp fused
        body — explicitly, logged once in :attr:`policy` (this used to be
        a silent undocumented drop / a hard raise)."""
        if self._mm_vmem_fallback() and "; spmm:" not in self.policy:
            via = ("jnp fused body" if self.variant == "fused"
                   else "scan-decode body")
            self.policy += (
                f"; spmm: m={self.m} > REPRO_FULL_X_LIMIT="
                f"{_FULL_X_LIMIT} breaks multi-RHS VMEM residency — "
                f"routed to {via}")
            _obs.inc("spmv.mm_fallback", variant=self.variant)
        if self.ephemeral or _is_traced(mat):
            return self._execute_mm(mat, self._device_operands(), x,
                                    permuted)
        if _obs.enabled() and not isinstance(x, jax.core.Tracer):
            span, bump = self._obs_handles(mat, "spmm")
            with span():
                bump()
                return self._dispatch("spmm")(self._exec_mat(mat),
                                              self._device_operands(), x,
                                              permuted)
        return self._dispatch("spmm")(self._exec_mat(mat),
                                      self._device_operands(), x,
                                      permuted)

    def as_composite(self, mat: PackSELLMatrix):
        """This plan as the single-member case of the block-composition
        engine (:class:`~repro.kernels.composite.CompositePlan`) — the
        degenerate composition mixed-precision and distributed SpMV build
        on."""
        from . import composite
        return composite.CompositePlan.single(mat, self)

    def validate(self, mat: PackSELLMatrix | None = None, *,
                 raise_: bool = True) -> list:
        """Full structural validation of the plan's derived operands
        (checkpoint monotonicity/range, fused-stream accounting, offset
        range, permutation bijectivity) — the on-demand deep check;
        :func:`_quick_validate` already ran the cheap subset at build.
        Returns the issue list (``raise_=False``) or raises
        ``robust.guard.IntegrityError``."""
        from repro.robust import guard as _guard

        if mat is None:
            mat = self._matref() if self._matref is not None else None
        if mat is None:
            raise ValueError("cannot validate: matrix is gone; pass mat=")
        return _guard.validate_plan(mat, self, raise_=raise_)

    def describe(self) -> dict:
        """Machine-readable plan summary (serving warmup logs, and the
        precision store's retile records key off this)."""
        return {"variant": self.variant, "policy": self.policy,
                "tiles": [list(t) for t in self.tiles], "hw": self.hw,
                "interpret": self.interpret, "n": self.n, "m": self.m,
                "total_stored": self.total_stored,
                "cache_mode": self.cache_mode,
                "cursor_cache": self.cols is not None,
                "fused": self.fused is not None,
                "ckpt_width": (None if self.fused_layout is None
                               else self.fused_layout.wr),
                "window_width": self.window_width}

    def decode_cache_stats(self) -> dict:
        """Decode-cache device memory, priced against the PR-1 full cursor
        cache (4 bytes per bucketed word) — the accounting behind the
        BENCH_spmv.json footprint trajectory (DESIGN.md §10.3).

        ``decode_cache_bytes`` is the per-matvec *auxiliary* decode stream
        (cursors or checkpoints); ``fused_stream_bytes`` is the repacked
        word stream, which REPLACES the bucketed packs on the hot path
        (same words ± run padding, streamed instead of them)."""
        full = 4 * self.total_words
        if self.cache_mode == "checkpoint" and self.fused_layout is not None:
            cache = self.fused_layout.checkpoint_bytes
            stream = self.fused_layout.stream_bytes
            pad = self.fused_layout.pad_words
        elif self.cache_mode == "checkpoint" and self.kckpts is not None:
            cache = sum(4 * int(np.prod(c.shape)) for c in self.kckpts)
            stream, pad = 0, 0
        elif self.cols is not None:
            cache, stream, pad = full, 0, 0
        else:
            cache, stream, pad = 0, 0, 0
        return dict(cache_mode=self.cache_mode,
                    decode_cache_bytes=cache,
                    full_cursor_bytes=full,
                    fused_stream_bytes=stream,
                    fused_pad_words=pad,
                    shrink_vs_full=(full / cache) if cache else float("inf"))

    # -- autotune hook -----------------------------------------------------
    def retile(self, tiles) -> None:
        """Install per-bucket ``(sb, wb)`` — or ``(sb, wb, wr)`` — winners
        (benchmarks/bench_kernels.py autotune). Band windows and
        width-block checkpoints are recomputed for the new tiles; a third
        element pins the fused-stream checkpoint width ``wr`` (plan-global
        — all triples must agree) and rebuilds the stream plus the
        σ-permutation maps when it changes. Jitted dispatch functions are
        invalidated and re-trace on next call."""
        tiles = tuple(tuple(int(v) for v in t) for t in tiles)
        if len(tiles) != len(self.tiles):
            raise ValueError(f"need {len(self.tiles)} (sb, wb[, wr]) "
                             "tuples")
        if any(len(t) not in (2, 3) for t in tiles):
            raise ValueError("tiles must be (sb, wb) or (sb, wb, wr)")
        wrs = {t[2] for t in tiles if len(t) == 3}
        if len(wrs) > 1:
            raise ValueError("the fused checkpoint width wr is plan-"
                             f"global; got conflicting values {sorted(wrs)}")
        new_wr = wrs.pop() if wrs else None
        tiles = tuple(t[:2] for t in tiles)
        mat = self._matref() if self._matref is not None else None
        if self.variant == "band":
            if mat is None:
                raise ValueError("cannot retile a band plan: matrix is gone")
            wins = []
            for (sb, _), d0, maxcol in zip(tiles, mat.d0s, mat.maxcols):
                win = bucket_band_windows(d0, maxcol, sb, self.hw)
                if win is None:
                    raise ValueError(
                        f"band kernel infeasible at sb={sb}, hw={self.hw}")
                wins.append(win)
            self.wins = tuple(wins)
        if self.kckpts is not None:
            if mat is None:
                raise ValueError("cannot retile checkpoints: matrix is gone")
            self.kckpts = jax.device_put(
                _build_block_checkpoints(mat, tiles))
        if (new_wr is not None and self.fused is not None
                and self.fused_layout is not None
                and new_wr != self.fused_layout.wr):
            if mat is None:
                raise ValueError(
                    "cannot re-width the fused stream: matrix is gone")
            fused, layout, orders = _build_fused_stream(
                mat, trim=self.fused_trim, wr=new_wr)
            if fused is None:
                raise ValueError(
                    f"wr={new_wr}: fused stream infeasible (group column "
                    "span overflows every compact offset encoding)")
            self.fused_layout = layout
            # the slice sort depends on runs-per-slice = f(wr): re-bake the
            # stored order and both inverse-permutation forms, and the
            # window operands follow the new stream
            outrow, inv, inv2 = _stored_order(mat, orders, True)
            W, window = (_plan_window(mat, fused, layout, None)
                         if self.variant == "jnp" and self.fused_trim
                         else (0, None))
            (self.fused, self.outrow_cat, self.inv_cat, self.inv2_cat,
             self.window) = jax.device_put((fused, outrow, inv, inv2,
                                            window))
            self.window_width = W
            self.tiles = tiles
            self._fns.clear()
            _quick_validate(mat, self)
            self._gauge_counts(mat)
            return
        self.tiles = tiles
        self._fns.clear()


# ---------------------------------------------------------------------------
# Plan construction + cache
# ---------------------------------------------------------------------------


def build_plan(mat: PackSELLMatrix, *, sb: int = 8, wb: int = 32,
               hw: int = _DEF_HW, force: str | None = None,
               interpret: bool | None = None,
               decode_cache: str | None = None,
               fused_trim: bool = True,
               ckpt_wr: int | None = None) -> SpMVPlan:
    """Host-side plan construction (the slow path — run once per matrix).

    ``decode_cache`` in {'checkpoint', 'full', '0'} (default: the
    ``REPRO_PLAN_CURSOR_CACHE`` env var, itself defaulting to
    'checkpoint') picks the decode-cache layout for the 'jnp' variant and
    whether the Pallas variants get width-block checkpoints.
    ``fused_trim=False`` keeps the fused layout shape-derived (no
    data-dependent slice sort / all-pad-run trimming) so SPMD consumers
    get identical layouts across shards. ``ckpt_wr=`` pins the fused
    checkpoint width instead of the modeled pick (the autotune sweep's
    third axis).
    """
    with _obs.host_span("packsell.plan_build"):
        plan = _build_plan(mat, sb=sb, wb=wb, hw=hw, force=force,
                           interpret=interpret, decode_cache=decode_cache,
                           fused_trim=fused_trim, ckpt_wr=ckpt_wr)
    if not plan.ephemeral:
        _obs.inc("plan.build", variant=plan.variant,
                 cache_mode=plan.cache_mode)
        plan._gauge_counts(mat)
    return plan


def _stored_order(mat: PackSELLMatrix, orders, two_d: bool):
    """Host arrays of the plan's stored order: ``outrow_cat`` (the fused
    layout's per-bucket slice sort ``orders`` baked in, where given), the
    inverse permutation and, where ``two_d``, its (slice, lane) form."""
    if orders is not None:
        outs = [np.asarray(o).reshape(len(ordr), -1)[ordr].reshape(-1)
                for o, ordr in zip(mat.outrows, orders)]
    else:
        outs = [np.asarray(o).reshape(-1) for o in mat.outrows]
    outrow = (np.concatenate(outs) if outs
              else np.zeros((0,), np.int32))
    inv = _build_inverse_perm(mat, outrow)
    inv2 = (np.stack([inv // mat.C, inv % mat.C], axis=1).astype(np.int32)
            if two_d else None)
    return outrow, inv, inv2


def _build_plan(mat: PackSELLMatrix, *, sb: int = 8, wb: int = 32,
                hw: int = _DEF_HW, force: str | None = None,
                interpret: bool | None = None,
                decode_cache: str | None = None,
                fused_trim: bool = True,
                ckpt_wr: int | None = None) -> SpMVPlan:
    interpret = _interpret_default() if interpret is None else interpret
    policy = (force or _env_policy()).lower()
    if policy not in _POLICIES:
        raise ValueError(f"force={policy!r} not in {_POLICIES}")
    mode = (decode_cache or _env_cache_mode()).lower()
    if mode not in _CACHE_MODES:
        raise ValueError(f"decode_cache={mode!r} not in {_CACHE_MODES}")
    n_buckets = len(mat.packs)
    tiles = tuple((sb, wb) for _ in range(n_buckets))

    src = f"force={force!r}" if force else "REPRO_SPMV_POLICY"
    if not interpret and policy in _PALLAS_VARIANTS:
        raise ValueError(
            f"{src}: the {policy!r} Pallas variant does not compile for a "
            f"TPU — {PALLAS_REFUSAL}; use force='jnp' (the 'auto' choice) "
            "or interpret=True")

    if _is_traced(mat):
        # Under jit tracing the host cannot inspect column metadata: band
        # feasibility is undecidable and the decode caches cannot be built,
        # so fall back to the scan-decode variant and never cache (the plan
        # holds tracers).
        if policy == "band":
            raise ValueError(
                "force='band' requires a concrete matrix (host-side window "
                "planning); build the plan outside jit via get_plan(mat)")
        variant = "jnp" if policy in ("auto", "jnp", "fused") else "full"
        return SpMVPlan(
            variant=variant,
            policy=f"{variant} (tracing: host-side band planning "
                   f"unavailable; policy={policy})",
            hw=hw, interpret=interpret, tiles=tiles, wins=None,
            outrow_cat=jnp.concatenate([o.reshape(-1) for o in mat.outrows])
            if n_buckets else jnp.zeros((0,), jnp.int32),
            n=mat.n, m=mat.m,
            total_stored=sum(int(p.shape[0]) * int(p.shape[2])
                             for p in mat.packs),
            cache_mode="0",
            ephemeral=True)

    wins = None
    fused, layout, orders = (None, None, None)
    if policy == "band":
        wins = band_plan(mat, sb, hw) if mat.m > 0 else None
        if wins is None:
            raise ValueError("band kernel infeasible for this matrix/hw")
        variant, reason = "band", f"forced via {src}"
    elif policy in ("full", "jnp"):
        variant, reason = policy, f"forced via {src}"
    elif policy == "fused":
        if mat.m > _FULL_X_LIMIT:
            raise ValueError(
                f"x too large for VMEM residency (m={mat.m}); the fused "
                "kernel gathers the whole x — use band/jnp")
        with _obs.host_span("packsell.plan_build.stream"):
            fused, layout, orders = _build_fused_stream(
                mat, trim=fused_trim, wr=ckpt_wr)
        if fused is None:
            # forced fused but no compact encoding fits: demote to the
            # jnp variant on the full cursor cache, loudly
            variant = "jnp"
            reason = (f"forced fused via {src} demoted to jnp: fused "
                      "stream infeasible (group column span overflows "
                      "every compact offset encoding)")
            mode = "full"
        else:
            variant, reason = "fused", f"forced via {src}"
    elif interpret:
        variant = "jnp"
        reason = ("auto: non-TPU backend — Pallas (incl. the fused-"
                  "stream kernel) would run in interpret mode, fused-"
                  "stream XLA path is faster (force='fused' runs the "
                  "interpret kernel anyway)")
    else:
        variant = "jnp"
        reason = f"auto: compiled TPU backend — {PALLAS_REFUSAL}"
    if variant == "full" and mat.m > _FULL_X_LIMIT:
        raise ValueError(
            f"x too large for VMEM residency (m={mat.m}); use band/jnp")

    cols = None
    kckpts = None
    if variant == "fused":
        if mode != "checkpoint":
            # the fused stream IS the decode cache: offsets are baked into
            # the words, checkpoints are the only auxiliary stream
            reason += (f"; decode_cache={mode!r} overridden to "
                       "'checkpoint' (the fused stream is the decode "
                       "cache)")
            mode = "checkpoint"
    elif variant == "jnp":
        if mode == "checkpoint" and fused is None:
            with _obs.host_span("packsell.plan_build.stream"):
                fused, layout, orders = _build_fused_stream(
                    mat, trim=fused_trim, wr=ckpt_wr)
            if fused is None:
                # a group's column span overflows every compact offset
                # encoding — fall back to the full cursor cache, loudly
                mode = "full"
                reason += ("; checkpoint stream infeasible (group column "
                           "span overflow), fell back to full cursor "
                           "cache")
        if mode == "full":
            with _obs.host_span("packsell.plan_build.cache"):
                cols = _build_cursor_cache(mat)
    elif mode == "checkpoint":
        with _obs.host_span("packsell.plan_build.cache"):
            kckpts = _build_block_checkpoints(mat, tiles)
    W, window = 0, None
    if variant == "jnp" and fused_trim:
        # the jnp decode bodies gather x through windows where the
        # matrix's spans make that cheaper; an untrimmed (SPMD-stacked)
        # plan keeps the scalar gather, its layout being shape-derived
        W, window = _plan_window(mat, fused, layout, cols)
    with _obs.host_span("packsell.plan_build.inverse"):
        # the fused layout's per-bucket slice sort is baked into the
        # stored order (outputs of the fused tail land in sorted order)
        outrow_cat, inv, inv2 = _stored_order(mat, orders,
                                              fused is not None)
    with _obs.host_span("packsell.plan_build.to_device"):
        fused, cols, kckpts, outrow_cat, inv, inv2, window = jax.device_put(
            (fused, cols, kckpts, outrow_cat, inv, inv2, window))
    plan = SpMVPlan(
        variant=variant, policy=f"{variant} ({reason})", hw=hw,
        interpret=interpret, tiles=tiles,
        wins=None if wins is None else tuple(wins),
        outrow_cat=outrow_cat, n=mat.n, m=mat.m,
        total_stored=sum(int(p.shape[0]) * int(p.shape[2])
                         for p in mat.packs),
        inv_cat=inv, inv2_cat=inv2,
        cols=cols, cache_mode=mode, fused=fused, fused_layout=layout,
        kckpts=kckpts, window=window, window_width=W,
        total_words=sum(int(np.prod(p.shape)) for p in mat.packs),
        fused_trim=fused_trim,
        _matref=weakref.ref(mat))
    _quick_validate(mat, plan)
    return plan


def _quick_validate(mat: PackSELLMatrix, plan: SpMVPlan) -> None:
    """Cheap build-time structural invariants (O(n) bincount + O(segments)
    accounting — no word decode; the deep pass is
    :meth:`SpMVPlan.validate`). A violation here is a construction bug,
    never input data: raise immediately rather than hand the kernels a
    plan that scatters out of bounds."""
    outrow = np.asarray(plan.outrow_cat)
    if len(outrow) != plan.total_stored:
        raise ValueError(
            f"plan build: outrow_cat length {len(outrow)} != total_stored "
            f"{plan.total_stored}")
    counts = np.bincount(outrow[outrow < plan.n], minlength=max(plan.n, 1))
    if plan.n and (counts[:plan.n].min() < 1 or counts[:plan.n].max() > 1):
        raise ValueError("plan build: outrow_cat is not a bijection onto "
                         "[0, n)")
    layout = plan.fused_layout
    if plan.fused is not None and layout is not None:
        w3, ck = plan.fused
        if tuple(w3.shape) != (layout.groups, layout.wr, layout.C):
            raise ValueError(
                f"plan build: fused stream shape {tuple(w3.shape)} != "
                f"layout ({layout.groups}, {layout.wr}, {layout.C})")
        if tuple(ck.shape) != (layout.groups, layout.C):
            raise ValueError(
                f"plan build: fused checkpoint shape {tuple(ck.shape)} != "
                f"({layout.groups}, {layout.C})")
        g_sum = sum(seg.groups for seg in layout.segments)
        if g_sum != layout.groups:
            raise ValueError(
                f"plan build: segment group accounting {g_sum} != "
                f"{layout.groups}")
        stored = sum(seg.stored for seg in layout.segments)
        if stored != plan.total_stored:
            raise ValueError(
                f"plan build: segment stored accounting {stored} != "
                f"{plan.total_stored}")


_PLANS: dict = {}
_STATS = {"hits": 0, "misses": 0, "evicted": 0}
_TOKENS = itertools.count()


def _plan_cache_cap() -> int:
    """Plan-cache capacity (env-tunable so serving processes stay
    bounded; read per call so tests can shrink it at runtime)."""
    try:
        return max(int(os.environ.get("REPRO_PLAN_CACHE_CAP", 256)), 1)
    except ValueError:
        return 256


def _plan_token(mat: PackSELLMatrix) -> int:
    """Monotonic per-matrix cache token. ``id(mat)`` is unusable as a key
    component: after GC reuses an address, the dead matrix's deferred
    weakref callback would evict the *new* matrix's freshly cached plan
    (same key). The token is assigned once per matrix object and never
    recycled, so keys of distinct matrices can never collide."""
    tok = getattr(mat, "_plan_token", None)
    if tok is None:
        tok = next(_TOKENS)
        mat._plan_token = tok
    return tok


def get_plan(mat: PackSELLMatrix, *, sb: int = 8, wb: int = 32,
             hw: int = _DEF_HW, force: str | None = None,
             interpret: bool | None = None,
             decode_cache: str | None = None,
             fused_trim: bool = True,
             ckpt_wr: int | None = None) -> SpMVPlan:
    """Cached plan lookup. Keyed on ``(mat._plan_token, sb, wb, hw, policy,
    interpret, decode-cache mode, trim, ckpt_wr)`` — a monotonically
    assigned per-matrix token (see :func:`_plan_token`); entries are
    dropped (weakref) when the matrix dies."""
    interpret = _interpret_default() if interpret is None else interpret
    policy = (force or _env_policy()).lower()
    mode = (decode_cache or _env_cache_mode()).lower()
    if _is_traced(mat):
        # tracer matrices are per-trace objects: build ephemeral, skip cache
        return build_plan(mat, sb=sb, wb=wb, hw=hw, force=force,
                          interpret=interpret, decode_cache=decode_cache)
    key = (_plan_token(mat), sb, wb, hw, policy, interpret, mode,
           fused_trim, ckpt_wr)
    ent = _PLANS.get(key)
    if ent is not None and ent[0]() is mat:
        _STATS["hits"] += 1
        _obs.inc("plan_cache.hit")
        _PLANS[key] = _PLANS.pop(key)       # move to MRU position
        return ent[1]
    plan = build_plan(mat, sb=sb, wb=wb, hw=hw, force=force,
                      interpret=interpret, decode_cache=decode_cache,
                      fused_trim=fused_trim, ckpt_wr=ckpt_wr)

    def _drop(_ref, key=key):
        if _PLANS.pop(key, None) is not None:
            _STATS["evicted"] += 1
            _obs.inc("plan_cache.evict", cause="matrix_dead")

    _PLANS[key] = (weakref.ref(mat, _drop), plan)
    _STATS["misses"] += 1
    _obs.inc("plan_cache.miss")
    # LRU bound: a long-running serving process cycling many matrices must
    # not grow without limit; an evicted plan rebuilds bit-identically
    # (build_plan is deterministic in (mat, key))
    cap = _plan_cache_cap()
    while len(_PLANS) > cap:
        _PLANS.pop(next(iter(_PLANS)))
        _STATS["evicted"] += 1
        _obs.inc("plan_cache.evict", cause="capacity")
    return plan


def cache_stats() -> dict:
    """Plan-cache counters; also the live source behind
    ``repro.observe.report()``'s ``plan_cache`` block — the registry's
    ``plan_cache.*`` event counters mirror the same increments."""
    return dict(_STATS, size=len(_PLANS))


def clear_cache() -> None:
    _PLANS.clear()
    _STATS.update(hits=0, misses=0, evicted=0)
