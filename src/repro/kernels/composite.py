"""CompositePlan: ONE block-composition engine for every SpMV path
(DESIGN.md §9).

Three subsystems used to re-implement the same recipe — "stack format
blocks → one jitted dispatch → global inverse-permutation gather":
``kernels/plan.py::SpMVPlan`` (width buckets of one matrix),
``precision/mixed.py::MixedPackSELL`` (per-row-class codec blocks) and
``distributed/plan.py::DistSpMVPlan`` (per-shard local/remote block pairs).
This module is the single composition layer the paper's unified SELL-C-σ
argument calls for (Kreutzer et al., arXiv:1307.6209; GPGPU-cluster
follow-up arXiv:1112.5588): the others are now thin wrappers.

Model
-----
A :class:`CompositePlan` is an ordered list of :class:`CompositeMember`\\ s.
Each member is one format block — a
:class:`~repro.core.packsell.PackSELLMatrix` executed through its
:class:`~repro.kernels.plan.SpMVPlan` in ``permuted=True`` (stored-row)
mode, or an uncompressed :class:`~repro.core.sell.SELLMatrix` (fp32/fp64)
executed by :func:`sell_stored_spmv` — annotated with

* ``rows``   — the stored→global row map (which global rows the block
  covers; ``None`` = block rows are already global rows),
* ``term``   — the **sum group**. Members of one term cover disjoint row
  sets; their stored outputs are concatenated and ONE precomputed global
  inverse-permutation *gather* per term produces a full-length vector.
  Terms are then **added** — the distributed ``y = A_loc x + A_rem x_halo``
  pattern, where the local and remote blocks both cover every row.
* ``x_index`` — which input vector the member consumes (0 = x; the
  distributed layer feeds the halo buffer as input 1, produced by the
  halo-exchange *pre-stage*, ``distributed/halo.py``).

So: mixed precision = one term, many members (concat + one gather);
distributed = two terms (local + remote), each one member; distributed ×
mixed = two terms, many members each — the composition the paper's
headline mixed-precision results need, previously structurally impossible.

The whole composite runs as ONE jitted dispatch; everything host-side
(member plans, term inverse permutations, coverage validation) happens at
build time. ``execute_with`` exposes the raw body for reuse inside an
existing trace (the ``shard_map`` hook, mirroring
:meth:`~repro.kernels.plan.SpMVPlan.execute_with`).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packsell as pk
from repro.core import sell as sl
from repro.core.packsell import PackSELLMatrix
from repro.core.sell import SELLMatrix
from repro.observe import metrics as _obs

from . import plan as kplan


# ---------------------------------------------------------------------------
# SELL member execution (stored-row order, gather-epilogue compatible)
# ---------------------------------------------------------------------------


def sell_stored_spmv(mat: SELLMatrix, x: jnp.ndarray, *,
                     multi_rhs: bool = False) -> jnp.ndarray:
    """One SELL block in **stored-row order** — the fp32/fp64 analogue of
    ``SpMVPlan.execute_with(..., permuted=True)``.

    Unlike :func:`repro.core.sell.sell_spmv_jnp` this emits the raw
    ``[S*C]`` slice outputs with NO per-block scatter; the composite's term
    inverse permutation (built from the block's ``outrows``) maps them to
    global rows in one gather. Compute dtype is the block's value dtype
    promoted to at least fp32, so fp32 blocks match ``sell_spmv_jnp(...,
    float32)`` bit-for-bit and fp64 blocks serve as exact operators.
    """
    cdt = jnp.promote_types(mat.vals[0].dtype, jnp.float32)
    xc = x.astype(cdt)
    parts = []
    for val, col in zip(mat.vals, mat.cols):
        S, w, C = val.shape
        if multi_rhs:
            nb = xc.shape[1]
            t0 = jnp.zeros((S, C, nb), cdt)

            def body_mm(j, t, val=val, col=col):
                v = val[:, j, :].astype(cdt)
                xv = jnp.take(xc, col[:, j, :], axis=0, mode="clip")
                return t + v[..., None] * xv

            t = jax.lax.fori_loop(0, w, body_mm, t0)
            parts.append(t.reshape(-1, nb))
        else:
            t0 = jnp.zeros((S, C), cdt)

            def body(j, t, val=val, col=col):
                v = val[:, j, :].astype(cdt)
                xv = jnp.take(xc, col[:, j, :], axis=0, mode="clip")
                return t + v * xv

            t = jax.lax.fori_loop(0, w, body, t0)
            parts.append(t.reshape(-1))
    if not parts:
        shape = (0, xc.shape[1]) if multi_rhs else (0,)
        return jnp.zeros(shape, cdt)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


# ---------------------------------------------------------------------------
# Members
# ---------------------------------------------------------------------------

#: codecs stored as uncompressed SELL value/column blocks
SELL_CODECS = ("fp32", "fp64")


@dataclasses.dataclass
class CompositeMember:
    """One format block inside a composite (see module docstring)."""

    mat: object                    # PackSELLMatrix | SELLMatrix
    plan: Optional[kplan.SpMVPlan]  # execution engine; None for SELL blocks
    codec: str
    D: int
    rows: Optional[np.ndarray] = None   # block row -> global row (ascending)
    x_index: int = 0
    term: int = 0
    label: str = ""

    @property
    def fmt(self) -> str:
        return "sell" if self.plan is None else "packsell"

    @property
    def stored(self) -> int:
        """Stored output slots this member emits."""
        if self.plan is not None:
            return self.plan.total_stored
        return sum(int(v.shape[0]) * int(v.shape[2]) for v in self.mat.vals)

    @property
    def block_n(self) -> int:
        return int(self.mat.n)

    def outrow_host(self) -> np.ndarray:
        """Host copy of the stored-slot → block-row map (sentinel >= n)."""
        if self.plan is not None:
            return np.asarray(self.plan.outrow_cat)
        outs = [np.asarray(o).reshape(-1) for o in self.mat.outrows]
        return (np.concatenate(outs) if outs
                else np.zeros((0,), np.int32))

    def device_operands(self) -> dict:
        """The member's plan-held device buffers (the fused checkpoint
        stream, or the legacy cursor cache). ``inv``/``outrow`` are None:
        the composite's term gather replaces the per-block epilogue."""
        if self.plan is None:
            return {}
        return {"cols": self.plan.cols, "inv": None, "outrow": None,
                "fused": self.plan.fused, "kckpt": self.plan.kckpts}

    def execute(self, mat, dev: dict, x: jnp.ndarray, *,
                multi_rhs: bool = False) -> jnp.ndarray:
        """Stored-row-order block output (inside an existing trace)."""
        if self.plan is None:
            return sell_stored_spmv(mat, x, multi_rhs=multi_rhs)
        return self.plan.execute_with(mat, dev, x, permuted=True,
                                      multi_rhs=multi_rhs)


def member_from_csr(sub, codec: str, D: int, *, C: int = 32,
                    sigma: int = 256, rows=None, x_index: int = 0,
                    term: int = 0, label: str = "",
                    bucket_strategy: str | None = None,
                    device: bool = True,
                    force: str | None = None) -> CompositeMember:
    """Build one member from a CSR block. ``codec`` in
    :data:`SELL_CODECS` builds an uncompressed SELL block; anything else a
    PackSELL block with its cached :class:`~repro.kernels.plan.SpMVPlan`."""
    if codec in SELL_CODECS:
        vd = {"fp32": "float32", "fp64": "float64"}[codec]
        mat = sl.from_csr(sub, C=C, sigma=sigma, value_dtype=vd,
                          bucket_strategy=bucket_strategy or "pow2",
                          device=device)
        splan = None
    else:
        mat = pk.from_csr(sub, C=C, sigma=sigma, D=D, codec=codec,
                          bucket_strategy=bucket_strategy or "pow2",
                          device=device)
        splan = (kplan.get_plan(mat) if device
                 else kplan.build_plan(mat, force=force or "jnp"))
    return CompositeMember(
        mat=mat, plan=splan, codec=codec, D=D,
        rows=None if rows is None else np.asarray(rows, np.int64),
        x_index=x_index, term=term, label=label or f"{codec}/D={D}")


# ---------------------------------------------------------------------------
# Term inverse permutations (the ONE-gather epilogue)
# ---------------------------------------------------------------------------


def term_inverse(n: int, members: Sequence[CompositeMember], *,
                 allow_uncovered: bool = False,
                 term: int = 0) -> np.ndarray:
    """``inv[r]`` = slot of global row r in the term's concatenated member
    outputs. Requires disjoint member row sets; rows no member covers are
    an error unless ``allow_uncovered`` — then they point at the appended
    all-zero pad slot (index = term's total stored), so uncovered rows read
    exactly 0 through the gather.
    """
    inv = np.full(n, -1, np.int64)
    off = 0
    for mem in members:
        out = mem.outrow_host()
        valid = out < mem.block_n
        blk = out[valid]
        g = blk if mem.rows is None else mem.rows[blk]
        if np.any(inv[g] >= 0):
            raise ValueError(
                f"composite members overlap in rows (term {term})")
        inv[g] = off + np.nonzero(valid)[0]
        off += mem.stored
    missing = inv < 0
    if np.any(missing):
        if not allow_uncovered:
            raise ValueError(
                f"composite members cover {int((~missing).sum())} of {n} "
                f"rows in term {term}; every row needs exactly one class")
        inv[missing] = off          # the zero pad slot
    return inv.astype(np.int32)


# ---------------------------------------------------------------------------
# Unified memory accounting (satellite: one blend for plain/mixed/dist)
# ---------------------------------------------------------------------------


def _block_bytes(mat) -> int:
    st = mat.memory_stats()
    return int(st.get("packsell_bytes", st.get("sell_bytes", 0)))


def composite_memory_stats(entries, *, halo: dict | None = None) -> dict:
    """Blend per-block memory stats into one profile with a per-member
    breakdown — THE accounting used by :meth:`CompositePlan.memory_stats`,
    ``MixedPackSELL.memory_stats`` and ``DistSpMVPlan.memory_stats``.

    ``entries``: iterable of ``(label, codec, D, n_rows, mats)`` where
    ``mats`` is one block or a per-shard list of blocks. ``halo``: optional
    communication profile merged in (the distributed layer's traffic).
    """
    members = []
    total_bytes = total_nnz = 0
    for label, codec, D, n_rows, mats in entries:
        mats = mats if isinstance(mats, (list, tuple)) else [mats]
        b = sum(_block_bytes(m) for m in mats)
        nnz = sum(int(m.nnz) for m in mats)
        members.append({
            "label": label, "codec": codec, "D": D, "rows": n_rows,
            "bytes": b, "nnz": nnz, "bytes_per_nnz": b / max(nnz, 1)})
        total_bytes += b
        total_nnz += nnz
    out = {
        "composite_bytes": total_bytes,
        "bytes_per_nnz": total_bytes / max(total_nnz, 1),
        "nnz": total_nnz,
        "members": members,
    }
    if halo:
        out.update(halo)
    return out


# ---------------------------------------------------------------------------
# The composite plan
# ---------------------------------------------------------------------------


class CompositePlan:
    """Ordered member blocks, one jitted dispatch, one gather per term.

    ``allow_uncovered=True`` (distributed shard composites: padding rows
    beyond the shard's real row count) routes uncovered rows to an
    appended all-zero pad slot instead of raising.
    """

    def __init__(self, members: Sequence[CompositeMember], n: int, m: int,
                 *, allow_uncovered: bool = False, name: str = "composite"):
        self.members = list(members)
        if not self.members:
            raise ValueError("composite needs at least one member")
        self.n = int(n)
        self.m = int(m)
        self.name = name
        self.pad_slot = bool(allow_uncovered)
        terms = sorted({mem.term for mem in self.members})
        if terms != list(range(len(terms))):
            raise ValueError(f"member terms must be 0..T-1, got {terms}")
        self.n_terms = len(terms)
        self.n_inputs = 1 + max(mem.x_index for mem in self.members)
        # coverage/overlap validation happens eagerly (host numpy); the
        # device copies are built lazily — shard_map templates supply
        # per-shard inverses through execute_with and never need these
        self._invs_np = tuple(
            term_inverse(self.n,
                         [mm for mm in self.members if mm.term == t],
                         allow_uncovered=allow_uncovered, term=t)
            for t in range(self.n_terms))
        self._invs: Optional[tuple] = None
        self.nnz = sum(int(mem.mat.nnz) for mem in self.members)
        self._fns: dict = {}
        self._cat: Optional[tuple] = None
        self._cat_built = False

    @property
    def invs(self) -> tuple:
        """Per-term inverse permutations on device (lazy)."""
        if self._invs is None:
            self._invs = tuple(jnp.asarray(v) for v in self._invs_np)
        return self._invs

    def validate(self, *, raise_: bool = True) -> list:
        """Structural integrity check over every member block and term
        inverse (robust.guard.validate_composite). Returns the list of
        problem strings (empty when clean); raises IntegrityError instead
        when ``raise_`` is set."""
        from repro.robust import guard as _guard
        return _guard.validate_composite(self, raise_=raise_)

    # -- operand plumbing --------------------------------------------------
    def member_mats(self) -> tuple:
        return tuple(mem.mat for mem in self.members)

    def member_devs(self) -> tuple:
        return tuple(mem.device_operands() for mem in self.members)

    def fused_cat(self) -> Optional[tuple]:
        """ONE concatenated word-stream operand for the whole composite
        (lazy): every fused member's ``(words3d, ckpt)`` flattened into a
        single ``(words_cat, ckpt_cat, slices)`` pair of device buffers
        plus static slice metadata. The jitted dispatch streams one
        operand for all member blocks; members that carry no fused stream
        (SELL blocks, cursor/scan plans) keep their own operands. None
        when no member is fused."""
        if not self._cat_built:
            self._cat_built = True
            ws, cks, slices = [], [], []
            w_off = c_off = 0
            for mem in self.members:
                fz = None if mem.plan is None else mem.plan.fused
                if fz is None:
                    slices.append(None)
                    continue
                w3, ck = fz
                slices.append((w_off, tuple(w3.shape), c_off,
                               tuple(ck.shape)))
                ws.append(w3.reshape(-1))
                cks.append(ck.reshape(-1))
                w_off += int(np.prod(w3.shape))
                c_off += int(np.prod(ck.shape))
            # one fused member needs no concatenation — and the cat is a
            # real device copy next to the (possibly shared) member plans'
            # own streams, so only pay it when it actually merges operands
            if len(ws) >= 2:
                self._cat = (jnp.concatenate(ws), jnp.concatenate(cks),
                             tuple(slices))
        return self._cat

    def _devs_with_cat(self, devs, cat):
        """Rebuild per-member dev dicts from the concatenated word-stream
        operand (static slices — XLA fuses them into the consumers). The
        slice table is composite-static (``self._cat``); only the two
        buffers flow as jit arguments."""
        wcat, ckcat = cat
        slices = self._cat[2]
        out = []
        for dev, sl in zip(devs, slices):
            if sl is None:
                out.append(dev)
                continue
            w_off, wsh, c_off, csh = sl
            nd = dict(dev)
            nd["fused"] = (
                jax.lax.slice(wcat, (w_off,),
                              (w_off + int(np.prod(wsh)),)).reshape(wsh),
                jax.lax.slice(ckcat, (c_off,),
                              (c_off + int(np.prod(csh)),)).reshape(csh))
            out.append(nd)
        return tuple(out)

    # -- execution body ----------------------------------------------------
    def _execute(self, mats, devs, invs, xs, multi_rhs, cat=None):
        if cat is not None:
            devs = self._devs_with_cat(devs, cat)
        parts = [[] for _ in range(self.n_terms)]
        for mem, mat, dev in zip(self.members, mats, devs):
            t = mem.execute(mat, dev, xs[mem.x_index], multi_rhs=multi_rhs)
            parts[mem.term].append(t)
        y = None
        # each term's one inverse-permutation gather is the composite's
        # epilogue, named as a single plan's is
        with _obs.span("packsell.gather_epilogue"):
            for term_parts, inv in zip(parts, invs):
                t_cat = (term_parts[0] if len(term_parts) == 1
                         else jnp.concatenate(term_parts))
                if self.pad_slot:
                    pad = jnp.zeros((1,) + tuple(t_cat.shape[1:]),
                                    t_cat.dtype)
                    t_cat = jnp.concatenate([t_cat, pad])
                # each covered row has exactly one term slot (unique
                # indices; with a pad slot the uncovered rows share it, so
                # the hint is only safe without one)
                yt = jnp.take(t_cat, inv, axis=0, mode="clip",
                              unique_indices=not self.pad_slot)
                y = yt if y is None else y + yt
        return y

    def execute_with(self, mats, devs, invs, xs, *,
                     multi_rhs: bool = False) -> jnp.ndarray:
        """Run the composition body with externally supplied operands
        inside an existing trace — the shard_map reuse hook. The
        distributed layer stacks every member's arrays along the mesh axis
        and calls this with each shard's slices; the composite's static
        decisions (member order, terms, per-member plan statics) are reused
        across shards.

        ``mats``/``devs``: per-member block views and device-buffer dicts;
        ``invs``: per-term inverse permutations; ``xs``: the input vectors
        (``xs[mem.x_index]`` feeds each member — index 1 is the
        halo-exchange pre-stage output in the distributed composition).
        """
        return self._execute(mats, devs, invs, xs, multi_rhs)

    # -- public dispatch ---------------------------------------------------
    def _dispatch(self, multi_rhs: bool):
        fn = self._fns.get(multi_rhs)
        if fn is None:
            fn = jax.jit(lambda mats, devs, invs, xs, cat, mr=multi_rhs:
                         self._execute(mats, devs, invs, xs, mr, cat=cat))
            self._fns[multi_rhs] = fn
        return fn

    def _run_args(self):
        """(mats, devs, invs, cat): with a concatenated word stream the
        per-member dev dicts drop their fused arrays — the single cat
        operand carries them all. Fused members ship their plan's
        placeholder-leaf matrix view (the body reads only codec statics),
        keeping the dispatch pytree small."""
        devs = self.member_devs()
        mats = tuple(mem.mat if mem.plan is None
                     else mem.plan._exec_mat(mem.mat)
                     for mem in self.members)
        cat = self.fused_cat()
        if cat is not None:
            devs = tuple(
                {**dev, "fused": None} if sl is not None else dev
                for dev, sl in zip(devs, cat[2]))
            cat = cat[:2]
        return mats, devs, self.invs, cat

    def _run(self, x: jnp.ndarray, multi_rhs: bool) -> jnp.ndarray:
        if self.n_inputs != 1:
            raise ValueError(
                "composite has members on input index > 0 (a distributed "
                "halo composition); drive it via execute_with")
        mats, devs, invs, cat = self._run_args()
        if isinstance(x, jax.core.Tracer):
            return self._execute(mats, devs, invs, (x,), multi_rhs,
                                 cat=cat)
        _obs.inc("composite.dispatch", composite=self.name,
                 kind="spmm" if multi_rhs else "spmv",
                 members=len(self.members), terms=self.n_terms)
        return self._dispatch(multi_rhs)(mats, devs, invs, (x,), cat)

    def spmv(self, x: jnp.ndarray) -> jnp.ndarray:
        """y = A x — one jitted dispatch over every member block."""
        return self._run(x, False)

    def spmm(self, x: jnp.ndarray) -> jnp.ndarray:
        """Y = A X for X: [m, nb] (every member's multi-RHS path)."""
        return self._run(x, True)

    @property
    def matvec(self):
        return self.spmv

    @property
    def shape(self):
        return (self.n, self.m)

    # -- unified plumbing --------------------------------------------------
    def warmup(self, nb: int = 0) -> "CompositePlan":
        """Trace the dispatch(es) ahead of the first real call (the serving
        engine's WarmupSpec contract)."""
        jax.block_until_ready(self.spmv(jnp.zeros((self.m,), jnp.float32)))
        if nb:
            jax.block_until_ready(
                self.spmm(jnp.zeros((self.m, nb), jnp.float32)))
        return self

    def memory_stats(self, *, halo: dict | None = None) -> dict:
        return composite_memory_stats(
            [(mem.label, mem.codec, mem.D,
              mem.block_n if mem.rows is None else len(mem.rows), mem.mat)
             for mem in self.members], halo=halo)

    def describe(self) -> dict:
        """Machine-readable composite summary (warmup logs, stores)."""
        return {
            "name": self.name, "n": self.n, "m": self.m,
            "terms": self.n_terms, "inputs": self.n_inputs,
            "members": [{
                "label": mem.label, "fmt": mem.fmt, "codec": mem.codec,
                "D": mem.D, "term": mem.term, "x_index": mem.x_index,
                "stored": mem.stored,
                "plan": None if mem.plan is None
                else mem.plan.describe()["variant"],
            } for mem in self.members],
        }

    def retile(self, member: int, tiles) -> None:
        """Install autotuned (sb, wb) winners into one member's plan and
        invalidate the composite dispatch (re-traces on next call)."""
        splan = self.members[member].plan
        if splan is None:
            raise ValueError(f"member {member} is a SELL block (no plan)")
        splan.retile(tiles)
        self._fns.clear()

    # -- constructors ------------------------------------------------------
    @classmethod
    def single(cls, mat, plan: kplan.SpMVPlan | None = None
               ) -> "CompositePlan":
        """The degenerate one-member composite: ``SpMVPlan`` (or a SELL
        matrix) as the single-member case of the composition engine."""
        if isinstance(mat, PackSELLMatrix):
            plan = plan or kplan.get_plan(mat)
            mem = CompositeMember(mat=mat, plan=plan, codec=mat.codec_name,
                                  D=mat.D, label=f"{mat.codec_name}/"
                                                 f"D={mat.D}")
        elif isinstance(mat, SELLMatrix):
            codec = {"float32": "fp32", "float64": "fp64"}.get(
                mat.value_dtype, mat.value_dtype)
            mem = CompositeMember(mat=mat, plan=None, codec=codec, D=0,
                                  label=codec)
        else:
            raise TypeError(f"cannot wrap {type(mat).__name__}")
        return cls([mem], n=mat.n, m=mat.m, name="single")

    @classmethod
    def from_classes(cls, a, classes, *, C: int = 32, sigma: int = 256,
                     name: str = "mixed") -> "CompositePlan":
        """Row-class composition over one CSR matrix: each ``(codec, D,
        rows)`` class becomes a member over its row submatrix (full column
        space — x is shared), all in one term. The MixedPackSELL layout."""
        a = a.tocsr()
        a.sort_indices()
        n = a.shape[0]
        members = []
        for cls_i in classes:
            codec, D, rows = cls_i
            rows = (np.arange(n, dtype=np.int64) if rows is None
                    else np.asarray(rows, dtype=np.int64))
            members.append(member_from_csr(
                a[rows], codec, D, C=C, sigma=sigma, rows=rows))
        return cls(members, n=n, m=a.shape[1], name=name)
