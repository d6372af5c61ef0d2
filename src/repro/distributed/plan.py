"""DistSpMVPlan: one jitted shard_map dispatch for distributed SpMV
(DESIGN.md §7.3, §9).

Layering mirrors the single-device engine (``kernels/plan.py``): every
host-side decision happens once at build time, the hot path is a single
jitted call. Since PR 4 the per-shard execution body is the shared
block-composition engine (:class:`~repro.kernels.composite.CompositePlan`,
DESIGN.md §9) — the local/remote block pair is a two-**term** composite
(local members consume the resident x-block, remote members the
halo-exchange pre-stage output; each term ends in ONE inverse-permutation
gather, terms add). Members may themselves be per-precision-class blocks
(``classes=`` / ``pplan=``), which is what makes **distributed ×
mixed-precision** compose: ``dist_mixed:<budget>`` operators and
``cg.adaptive_pcg_dist``.

* :func:`build_composite_operands` partitions the matrix
  (``partition.py``), builds per-shard per-class blocks (PackSELL for
  packed codecs, uncompressed SELL for fp32/fp64), pads every member to
  one static ``[S, w, C]`` shape across shards
  (``core.packsell.pad_uniform`` / ``core.sell.pad_uniform``), and
  **stacks** each member's device operands along a leading shard axis —
  plus per-term inverse permutations, the halo-exchange index maps
  (``halo.py``) and a row-validity mask.
* :class:`DistSpMVPlan` places the stacked operands on a 1-D device mesh
  and jits ONE ``shard_map`` dispatch per entry point. Inside the mapped
  body each shard slices its row of every operand and reuses the template
  composite via :meth:`~repro.kernels.composite.CompositePlan.execute_with`
  — plan reuse inside shard_map, no per-trace replanning.
* The body issues the halo gather FIRST (the composite *pre-stage*), then
  the members: XLA's scheduler can overlap the collective with the local
  compute, the communication/computation overlap of the Kreutzer-et-al.
  recipe.
* :func:`build_dist_tiers` stacks one member set per codec tier over ONE
  shared partition — the distributed tier ladder ``adaptive_pcg_dist``
  promotes through via ``lax.switch``.
* Both builders run under the host span ``packsell.dist_build`` (stages
  ``.partition``, ``.members``, ``.stack``, ``.to_device``); a
  ``DistSpMVPlan`` sets the gauges ``dist.shards``, ``dist.halo_entries``,
  ``dist.h_pad`` and ``dist.remote_nnz`` when it is built.

``reference_spmv`` replays the exact same stacked operands shard-by-shard
on the host (no mesh, no collectives) — the oracle that lets partition and
map construction be tested on a single device.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import packsell as pk
from repro.core import sell as sl
from repro.kernels import composite as kc
from repro.kernels import plan as kplan
from repro.observe import metrics as _obs
from repro.parallel.sharding import make_shard_mesh, shard_map_unchecked

from . import halo as dh
from . import partition as dp

_ceil_to = pk._ceil_to

#: stacked-operand keys shared by every member set (halo maps + row mask)
SHARED_KEYS = ("rowmask", "halo_src", "send_idx", "recv_slot")


@dataclasses.dataclass
class DistMember:
    """One composite member's per-shard blocks + stacked host arrays.

    All shards share one static block shape (padded), one codec, one term
    and one input index; the per-shard ``rows_local`` maps (block row →
    shard-local row) are baked into the stacked per-term inverse
    permutations.
    """

    key: str                   # host-dict prefix, e.g. 'm0'
    fmt: str                   # 'packsell' | 'sell'
    codec: str
    D: int
    term: int                  # 0 = local, 1 = remote
    x_index: int               # 0 = x_loc, 1 = x_halo (pre-stage output)
    label: str
    mats: list                 # per-shard padded host blocks
    plans: list | None         # per-shard SpMVPlans (PackSELL members)
    rows_local: list           # per-shard int64 shard-local row ids
    #                            (None = all shard rows, identity map)

    def n_rows(self) -> int:
        """Rows this member covers, summed over shards."""
        return sum(int(m.n) if r is None else len(r)
                   for r, m in zip(self.rows_local, self.mats))

    def shard_member(self, p: int) -> kc.CompositeMember:
        """This member's shard-p block as a CompositeMember (shard 0 is
        the composite template; the others feed inverse-perm builds)."""
        return kc.CompositeMember(
            mat=self.mats[p],
            plan=None if self.plans is None else self.plans[p],
            codec=self.codec, D=self.D, rows=self.rows_local[p],
            x_index=self.x_index, term=self.term, label=self.label)

    def host_arrays(self) -> dict:
        """Stacked [P, ...] device operands for the shard_map body.

        With the fused checkpoint stream (the default decode cache) the
        member ships ONLY ``{k}_fwords``/``{k}_fckpt`` — the packs/d0s are
        not read by the fused execution body, so stacking them would
        double the fleet's device footprint for nothing."""
        k = self.key
        if self.fmt == "packsell":
            if self.plans[0].fused is not None:
                w3, ck = zip(*(p.fused for p in self.plans))
                return {f"{k}_fwords": np.stack([np.asarray(w) for w in w3]),
                        f"{k}_fckpt": np.stack([np.asarray(c) for c in ck])}
            out = {f"{k}_pack": np.stack([np.asarray(m.packs[0])
                                          for m in self.mats]),
                   f"{k}_d0": np.stack([np.asarray(m.d0s[0])
                                        for m in self.mats])}
            if self.plans[0].cols is not None:
                out[f"{k}_cols"] = np.stack([np.asarray(p.cols[0])
                                             for p in self.plans])
            return out
        return {f"{k}_val": np.stack([np.asarray(m.vals[0])
                                      for m in self.mats]),
                f"{k}_col": np.stack([np.asarray(m.cols[0])
                                      for m in self.mats])}


def _normalize_classes(classes) -> list:
    """Accept ``(codec, D, rows|None)`` tuples or PrecisionClass objects."""
    out = []
    for c in classes:
        if isinstance(c, (tuple, list)):
            codec, D, rows = (c + (None,))[:3] if isinstance(c, tuple) \
                else (list(c) + [None])[:3]
        else:
            codec, D, rows = c.codec, c.D, c.rows
        out.append((codec, int(D),
                    None if rows is None else np.asarray(rows, np.int64)))
    return out


def _build_dist_member(idx: int, blocks, rows_local, codec: str, D: int, *,
                       C: int, sigma: int, term: int,
                       x_index: int, label: str) -> DistMember:
    """Build one member's per-shard blocks padded to a common shape."""
    if codec in kc.SELL_CODECS:
        vd = {"fp32": "float32", "fp64": "float64"}[codec]
        raw = [sl.from_csr(b, C=C, sigma=sigma, value_dtype=vd,
                           bucket_strategy="uniform", device=False)
               for b in blocks]
        S = max(int(m.vals[0].shape[0]) for m in raw)
        w = max(int(m.vals[0].shape[1]) for m in raw)
        mats = [sl.pad_uniform(m, n_slices=S, width=w, device=False)
                for m in raw]
        plans = None
    else:
        raw = [pk.from_csr(b, C=C, sigma=sigma, D=D, codec=codec,
                           bucket_strategy="uniform", device=False)
               for b in blocks]
        S = max(int(m.packs[0].shape[0]) for m in raw)
        w = max(int(m.packs[0].shape[1]) for m in raw)
        mats = [pk.pad_uniform(m, n_slices=S, width=w, device=False)
                for m in raw]
        # fused_trim=False: the fused layout must be shape-derived so all
        # shards share one static layout (shapes are pad_uniform'd equal).
        # REPRO_SPMV_POLICY=fused rides the fused Pallas kernel inside the
        # shard bodies; the default stays the jnp fused-stream body.
        force_v = "fused" if kplan._env_policy() == "fused" else "jnp"
        plans = [kplan.build_plan(m, force=force_v, fused_trim=False)
                 for m in mats]
        # ... but the ENCODING is still data-dependent (column-span
        # overflow falls back per shard), so any mismatch demotes the
        # whole member to the full cursor cache
        lays = {(None if p.fused_layout is None else
                 (p.fused_layout.wr, p.fused_layout.encoding))
                for p in plans}
        if len(lays) > 1:
            plans = [kplan.build_plan(m, force="jnp", decode_cache="full")
                     for m in mats]
    return DistMember(key=f"m{idx}", fmt="sell" if plans is None
                      else "packsell", codec=codec, D=D, term=term,
                      x_index=x_index, label=label, mats=mats, plans=plans,
                      rows_local=rows_local)


@dataclasses.dataclass
class DistOperands:
    """Mesh-independent distributed operands: the partition, the halo maps,
    the per-shard member blocks, the shard-0 composite template, and every
    stacked host array the shard_map body consumes (leading dim = shard)."""

    part: dp.RowPartition
    maps: dh.HaloMaps
    n: int
    n_pad: int                 # padded rows == padded local x length
    h_pad: int                 # padded halo buffer length (0: no halo)
    C: int
    sigma: int
    D: int
    codec: str                 # 'mixed' for multi-class member sets
    classes: list              # [(codec, D, rows|None)] build record
    host: dict                 # str -> np.ndarray [P, ...]
    members: list              # list[DistMember]
    tpl: kc.CompositePlan      # shard-0 template (identical statics ∀ shards)

    # -- back-compat views --------------------------------------------------
    @property
    def mats_loc(self) -> list:
        """Per-shard local blocks, flattened over members."""
        return [m for dm in self.members if dm.x_index == 0
                for m in dm.mats]

    @property
    def mats_rem(self) -> list:
        return [m for dm in self.members if dm.x_index == 1
                for m in dm.mats]

    # -- vector layout (host) ----------------------------------------------
    def stack_vector(self, v: np.ndarray) -> np.ndarray:
        """Global [n(, nb)] → stacked padded [P, n_pad(, nb)] (zeros pad)."""
        v = np.asarray(v)
        out = np.zeros((self.part.n_shards, self.n_pad) + v.shape[1:],
                       v.dtype)
        for p in range(self.part.n_shards):
            r0, r1 = self.part.rows_of(p)
            out[p, :r1 - r0] = v[r0:r1]
        return out

    def unstack_vector(self, ys: np.ndarray) -> np.ndarray:
        """Stacked padded [P, n_pad(, nb)] → global [n(, nb)]."""
        ys = np.asarray(ys)
        return np.concatenate([ys[p, :c]
                               for p, c in enumerate(self.part.counts)])

    # -- the per-shard SpMV body -------------------------------------------
    def _member_view(self, dm: DistMember, ops: dict):
        """A format-block view over this shard's operand slices. Only the
        fields the composite execution path reads are meaningful;
        accounting fields are 0 / shard-0 statics."""
        t = dm.mats[0]
        if dm.fmt == "packsell":
            if f"{dm.key}_fwords" in ops:
                # fused checkpoint stream: the execution body never reads
                # the packs, so the view carries placeholder leaves
                d0 = jnp.zeros((1,), jnp.int32)
            else:
                d0 = ops[f"{dm.key}_d0"]
            pack = ops.get(f"{dm.key}_pack",
                           jnp.zeros((1, 1, 1), jnp.uint32))
            return pk.PackSELLMatrix(
                packs=(pack,), d0s=(d0,), outrows=(d0,),
                maxcols=(jnp.zeros_like(d0),),
                perm=jnp.zeros((1,), jnp.uint8),
                n=t.n, m=t.m, C=self.C, sigma=self.sigma, D=dm.D,
                codec_name=dm.codec, k_left=0, nnz=0, n_dummy=0,
                words_sell_padded=0, words_bucketed=0)
        return sl.SELLMatrix(
            vals=(ops[f"{dm.key}_val"],), cols=(ops[f"{dm.key}_col"],),
            outrows=(jnp.zeros((1,), jnp.int32),),
            perm=jnp.zeros((1,), jnp.uint8),
            n=t.n, m=t.m, C=self.C, sigma=self.sigma,
            value_dtype=t.value_dtype, nnz=0, words_sell_padded=0,
            words_bucketed=0)

    def _member_dev(self, dm: DistMember, ops: dict) -> dict:
        if dm.fmt != "packsell":
            return {}
        cols = ops.get(f"{dm.key}_cols")
        fw = ops.get(f"{dm.key}_fwords")
        return {"cols": None if cols is None else (cols,),
                "inv": None, "outrow": None,
                "fused": None if fw is None
                else (fw, ops[f"{dm.key}_fckpt"])}

    def shard_body(self, ops: dict, x: jnp.ndarray, *,
                   axis_name: str | None, mode: str,
                   multi_rhs: bool = False,
                   x_halo: jnp.ndarray | None = None,
                   shared: dict | None = None) -> jnp.ndarray:
        """One shard's ``y_p = Σ_term (gather ∘ concat ∘ members)`` via the
        composite template (masked).

        Runs inside a shard_map body (``axis_name`` names the mesh axis the
        collectives run over) or standalone when ``x_halo`` is supplied
        (:func:`reference_spmv`, and the tier ladder whose pre-stage is
        hoisted out of the ``lax.switch``). The halo gather — the composite
        *pre-stage* — is issued before the member matvecs so the collective
        can overlap the resident-block compute. ``shared`` optionally
        supplies the halo maps / row mask when this member set's host dict
        carries only member arrays (the tier-ladder layout).
        """
        sh = ops if shared is None else shared
        xs = (x,)
        if self.h_pad > 0:
            if x_halo is None:
                x_halo = dh.gather_halo(
                    x, sh, axis_name=axis_name,
                    n_shards=self.part.n_shards, h_pad=self.h_pad,
                    mode=mode)
            xs = (x, x_halo)
        mats = tuple(self._member_view(dm, ops) for dm in self.members)
        devs = tuple(self._member_dev(dm, ops) for dm in self.members)
        invs = tuple(ops[f"inv{t}"] for t in range(self.tpl.n_terms))
        y = self.tpl.execute_with(mats, devs, invs, xs, multi_rhs=multi_rhs)
        mask = sh["rowmask"]
        return y * (mask[:, None] if multi_rhs else mask)


@dataclasses.dataclass
class _PartitionCtx:
    """One partition/split/halo-map build, shared by every member set
    over the same matrix and fleet size (the tier ladder builds T+1 sets;
    the CSR split and map construction only need to happen once)."""

    part: dp.RowPartition
    splits: list
    maps: dh.HaloMaps
    n_pad: int
    h_pad: int


def _partition_context(a: sp.csr_matrix, n_shards: int,
                       C: int) -> _PartitionCtx:
    with _obs.host_span("packsell.dist_build.partition"):
        part = dp.partition_rows(a.shape[0], n_shards)
        n_pad = _ceil_to(max(int(part.counts.max(initial=0)), 1), C)
        splits, h_pad = dp.split_csr(a, part, n_pad=n_pad)
        maps = dh.build_halo_maps(part, [s.halo_cols for s in splits],
                                  n_pad=n_pad, h_pad=h_pad)
    return _PartitionCtx(part=part, splits=splits, maps=maps, n_pad=n_pad,
                         h_pad=h_pad)


def build_composite_operands(a: sp.csr_matrix, n_shards: int, *,
                             classes, C: int = 32, sigma: int = 256,
                             ctx: _PartitionCtx | None = None
                             ) -> DistOperands:
    """Partition ``a`` over ``n_shards`` row blocks and build the stacked
    member operands for a per-class composite (host-side; no devices
    touched). ``classes``: ``(codec, D, rows|None)`` tuples or
    ``PrecisionClass`` objects whose row sets partition the global rows
    (``rows=None`` = all rows, single-class only). ``ctx`` reuses a
    precomputed :func:`_partition_context` (tier ladders share one)."""
    a = a.tocsr()
    n = a.shape[0]
    norm = _normalize_classes(classes)
    count = np.zeros(n, np.int64)
    for codec, D, rows in norm:
        if rows is None:
            count += 1
        else:
            count[rows] += 1
    if np.any(count != 1):
        raise ValueError(
            f"precision classes cover {int((count > 0).sum())} of {n} rows "
            f"(max multiplicity {int(count.max(initial=0))}); the classes "
            f"must partition the rows")

    ctx = ctx or _partition_context(a, n_shards, C)
    part, splits, maps = ctx.part, ctx.splits, ctx.maps
    n_pad, h_pad = ctx.n_pad, ctx.h_pad

    host = {
        "rowmask": (np.arange(n_pad)[None, :]
                    < part.counts[:, None]).astype(np.float32),
        "halo_src": maps.halo_src,
        "send_idx": maps.send_idx,
        "recv_slot": maps.recv_slot,
    }
    members: list[DistMember] = []
    sides = [("loc", 0, 0)] + ([("rem", 1, 1)] if h_pad > 0 else [])
    with _obs.host_span("packsell.dist_build.members"):
        for side, term, x_index in sides:
            for codec, D, rows in norm:
                mask = np.ones(n, bool) if rows is None else \
                    np.zeros(n, bool)
                if rows is not None:
                    mask[rows] = True
                blocks, rows_local = [], []
                for p in range(part.n_shards):
                    r0, r1 = part.rows_of(p)
                    src = (splits[p].a_loc if side == "loc"
                           else splits[p].a_rem)
                    if rows is None:
                        # all-rows class: the split block IS the member
                        # block (identity row map; no CSR fancy-index copy)
                        blocks.append(src)
                        rows_local.append(None)
                    else:
                        rl = np.nonzero(mask[r0:r1])[0].astype(np.int64)
                        blocks.append(src[rl])
                        rows_local.append(rl)
                members.append(_build_dist_member(
                    len(members), blocks, rows_local, codec, D, C=C,
                    sigma=sigma, term=term, x_index=x_index,
                    label=f"{side}:{codec}" + (
                        "" if codec in kc.SELL_CODECS else f"/D={D}")))
    with _obs.host_span("packsell.dist_build.stack"):
        for dm in members:
            host.update(dm.host_arrays())
        n_terms = 1 + (1 if h_pad > 0 else 0)
        for t in range(n_terms):
            tms = [dm for dm in members if dm.term == t]
            host[f"inv{t}"] = np.stack([
                kc.term_inverse(n_pad, [dm.shard_member(p) for dm in tms],
                                allow_uncovered=True, term=t)
                for p in range(part.n_shards)])
        tpl = kc.CompositePlan([dm.shard_member(0) for dm in members],
                               n=n_pad, m=n_pad, allow_uncovered=True,
                               name="dist")
    codec0, D0 = ((norm[0][0], norm[0][1]) if len(norm) == 1
                  else ("mixed", 0))
    return DistOperands(part=part, maps=maps, n=n, n_pad=n_pad, h_pad=h_pad,
                        C=C, sigma=sigma, D=D0, codec=codec0,
                        classes=norm, host=host, members=members, tpl=tpl)


def build_operands(a: sp.csr_matrix, n_shards: int, *, C: int = 32,
                   sigma: int = 256, D: int = 15,
                   codec: str = "fp16") -> DistOperands:
    """Single-class distributed operands (the historical entry point): one
    local + one remote member per shard at a fleet-wide ``(codec, D)``."""
    return build_composite_operands(a, n_shards,
                                    classes=[(codec, D, None)],
                                    C=C, sigma=sigma)


def reference_spmv(ops: DistOperands, x, mode: str = "all_gather",
                   multi_rhs: bool = False) -> np.ndarray:
    """Host oracle: replay the stacked operands shard-by-shard with the
    host-side exchange reference — no mesh, no collectives. Validates the
    partition, the maps, and the padded member blocks on a single device."""
    xs = ops.stack_vector(np.asarray(x, np.float32))
    xh = (dh.gather_halo_reference(xs, ops.maps, mode)
          if ops.h_pad > 0 else None)
    ys = []
    for p in range(ops.part.n_shards):
        ops_p = {k: jnp.asarray(v[p]) for k, v in ops.host.items()}
        y = ops.shard_body(
            ops_p, jnp.asarray(xs[p]), axis_name=None, mode=mode,
            multi_rhs=multi_rhs,
            x_halo=None if xh is None else jnp.asarray(xh[p]))
        ys.append(np.asarray(y))
    return ops.unstack_vector(np.stack(ys))


class _MeshBound:
    """Shared mesh-binding plumbing: device placement, in_specs, vector
    shard/unshard, and the build-once cache for jitted shard_map
    dispatches (``DistSpMVPlan`` and the tier ladder both use it)."""

    def _bind(self, ops_like, mesh, host: dict) -> None:
        if len(mesh.axis_names) != 1:
            raise ValueError(f"need a 1-D mesh, got axes {mesh.axis_names}")
        if mesh.devices.size != ops_like.part.n_shards:
            raise ValueError(
                f"mesh has {mesh.devices.size} devices but operands were "
                f"built for {ops_like.part.n_shards} shards")
        self._ops0 = ops_like
        self.mesh = mesh
        self.axis_name = mesh.axis_names[0]
        shard = NamedSharding(mesh, P(self.axis_name))
        with _obs.host_span("packsell.dist_build.to_device"):
            self.dev = jax.tree.map(
                lambda v: jax.device_put(v, shard), host)
        self._fns: dict = {}

    @property
    def n(self) -> int:
        return self._ops0.n

    @property
    def n_shards(self) -> int:
        return self._ops0.part.n_shards

    @property
    def dev_specs(self):
        """in_specs pytree for the stacked operands (leading shard axis)."""
        return jax.tree.map(lambda _: P(self.axis_name), self.dev)

    def cached_fn(self, key, builder):
        """Build-once cache for jitted shard_map dispatches (the
        distributed analogue of ``SpMVPlan._dispatch``; solvers park
        theirs here too)."""
        fn = self._fns.get(key)
        if fn is None:
            fn = builder()
            self._fns[key] = fn
        return fn

    def shard_vector(self, v) -> jnp.ndarray:
        """Global [n(, nb)] → device-sharded stacked [P, n_pad(, nb)]."""
        if isinstance(v, jax.core.Tracer):
            return self._shard_traced(v)
        return jax.device_put(
            self._ops0.stack_vector(np.asarray(v)),
            NamedSharding(self.mesh, P(self.axis_name)))

    def unshard_vector(self, ys) -> jnp.ndarray:
        if isinstance(ys, jax.core.Tracer):
            return self._unshard_traced(ys)
        return jnp.asarray(self._ops0.unstack_vector(np.asarray(ys)))

    def _shard_traced(self, v: jnp.ndarray) -> jnp.ndarray:
        """jnp mirror of ``stack_vector`` (static slices/pads only), used
        when the global vector is a tracer — a solver's loop-carried
        iterate. The jitted shard_map dispatch inlines into the enclosing
        trace, so ``dist_<codec>`` matvecs drop into unchanged solvers."""
        parts = []
        for p in range(self.n_shards):
            r0, r1 = self._ops0.part.rows_of(p)
            pad = [(0, self._ops0.n_pad - (r1 - r0))] + \
                [(0, 0)] * (v.ndim - 1)
            parts.append(jnp.pad(v[r0:r1], pad))
        return jnp.stack(parts)

    def _unshard_traced(self, ys: jnp.ndarray) -> jnp.ndarray:
        return jnp.concatenate(
            [ys[p, :int(c)] for p, c in enumerate(self._ops0.part.counts)])


class DistSpMVPlan(_MeshBound):
    """Stacked distributed operands bound to a 1-D device mesh, with one
    jitted ``shard_map`` dispatch per (entry point, exchange mode).

    Entry points take and return **global** vectors (``spmv`` / ``spmm``)
    or stay in the stacked-sharded layout (``spmv_sharded`` — solvers and
    benchmarks chain matvecs without host round-trips). ``shard_vector`` /
    ``unshard_vector`` convert between the two.
    """

    def __init__(self, ops: DistOperands, mesh, *,
                 exchange: str = "ppermute"):
        if exchange not in dh.EXCHANGE_MODES:
            raise ValueError(f"exchange={exchange!r} not in "
                             f"{dh.EXCHANGE_MODES}")
        self.ops = ops
        self.exchange = exchange
        self._bind(ops, mesh, ops.host)
        self._gauge_counts()

    def _gauge_counts(self) -> None:
        """Gauges set once when the plan is built: the shards, the halo
        entries the exchange fills per matvec (summed over shards,
        unpadded), the padded halo buffer length and the nonzeros of the
        remote members (those that read the halo)."""
        ops = self.ops
        _obs.gauge("dist.shards", self.n_shards)
        _obs.gauge("dist.halo_entries", int(ops.maps.counts.sum()))
        _obs.gauge("dist.h_pad", int(ops.h_pad))
        _obs.gauge("dist.remote_nnz", sum(
            int(m.nnz) for dm in ops.members if dm.x_index == 1
            for m in dm.mats))

    def _spmv_fn(self, mode: str, multi_rhs: bool):
        def build():
            ax = self.axis_name

            def body(dev, xs):
                o = jax.tree.map(lambda leaf: leaf[0], dev)
                y = self.ops.shard_body(o, xs[0], axis_name=ax, mode=mode,
                                        multi_rhs=multi_rhs)
                return y[None]

            f = shard_map_unchecked(body, self.mesh,
                                    in_specs=(self.dev_specs, P(ax)),
                                    out_specs=P(ax))
            return jax.jit(f)

        return self.cached_fn(("spmm" if multi_rhs else "spmv", mode), build)

    def spmv_sharded(self, xs: jnp.ndarray, *, mode: str | None = None,
                     multi_rhs: bool = False) -> jnp.ndarray:
        """Stacked-sharded [P, n_pad(, nb)] → same layout; one dispatch."""
        mode = mode or self.exchange
        if mode not in dh.EXCHANGE_MODES:
            # validate here, not only in gather_halo: halo-free partitions
            # (h_pad == 0) never reach the gather
            raise ValueError(f"mode={mode!r} not in {dh.EXCHANGE_MODES}")
        if _obs.enabled() and not isinstance(xs, jax.core.Tracer):
            _obs.inc("dist.dispatch", mode=mode, shards=self.n_shards,
                     kind="spmm" if multi_rhs else "spmv")
        return self._spmv_fn(mode, multi_rhs)(self.dev, xs)

    def spmv(self, x, *, mode: str | None = None) -> jnp.ndarray:
        """y = A @ x for a global [n] vector (shard → dispatch → unshard)."""
        return self.unshard_vector(self.spmv_sharded(
            self.shard_vector(x), mode=mode))

    def spmm(self, x, *, mode: str | None = None) -> jnp.ndarray:
        """Y = A @ X for a global [n, nb] block (multi-RHS path: one pass
        over each shard's packed words serves all nb right-hand sides)."""
        if np.ndim(x) != 2:
            raise ValueError(f"spmm expects [n, nb], got {np.shape(x)}")
        return self.unshard_vector(self.spmv_sharded(
            self.shard_vector(x), mode=mode, multi_rhs=True))

    def warmup(self, nb: int = 0, modes=None) -> "DistSpMVPlan":
        """Pre-trace the dispatches (serving-engine contract: the first
        tick pays neither tracing nor plan construction)."""
        for mode in (modes or (self.exchange,)):
            jax.block_until_ready(
                self.spmv(np.zeros(self.n, np.float32), mode=mode))
            if nb:
                jax.block_until_ready(
                    self.spmm(np.zeros((self.n, nb), np.float32), mode=mode))
        return self

    # -- accounting ---------------------------------------------------------
    def memory_stats(self) -> dict:
        """Fleet memory + communication profile via the unified composite
        blend (:func:`repro.kernels.composite.composite_memory_stats`):
        per-member breakdown over every shard's blocks, plus halo traffic
        and per-shard footprint extremes (partitioner load-balance
        signal)."""
        ops = self.ops
        st = kc.composite_memory_stats(
            [(dm.label, dm.codec, dm.D,
              dm.n_rows(), dm.mats)
             for dm in ops.members],
            halo={"shards": self.n_shards, "n_pad": ops.n_pad,
                  "h_pad": ops.h_pad,
                  "halo_entries": int(ops.maps.counts.sum()),
                  "halo_k_max": ops.maps.k_max,
                  "exchange": self.exchange})
        per_shard = [sum(kc._block_bytes(dm.mats[p]) for dm in ops.members)
                     for p in range(self.n_shards)]
        st["max_shard_bytes"] = max(per_shard) if per_shard else 0
        st["min_shard_bytes"] = min(per_shard) if per_shard else 0
        return st


def build_dist_plan(a: sp.csr_matrix, n_shards: int | None = None, *,
                    mesh=None, axis_name: str = "shards",
                    exchange: str = "ppermute", C: int = 32,
                    sigma: int = 256, D: int = 15, codec: str = "fp16",
                    classes=None, pplan=None,
                    devices=None) -> DistSpMVPlan:
    """Partition ``a`` across a 1-D device mesh and build the jitted
    distributed plan (the slow path — run once per matrix, like
    ``kernels.plan.build_plan``). With no mesh given, one shard per visible
    local device.

    ``classes`` (or ``pplan``, a rows-mode
    :class:`~repro.precision.select.PrecisionPlan`) builds a distributed ×
    mixed-precision composite: per-shard per-class members instead of one
    fleet-wide ``(codec, D)``.
    """
    if mesh is None:
        mesh = make_shard_mesh(n_shards, axis_name=axis_name,
                               devices=devices)
    if pplan is not None:
        if classes is not None:
            raise ValueError("pass either classes= or pplan=, not both")
        classes = [(c.codec, c.D, c.rows) for c in pplan.classes]
    if classes is None:
        classes = [(codec, D, None)]
    with _obs.host_span("packsell.dist_build"):
        ops = build_composite_operands(a, int(mesh.devices.size),
                                       classes=classes, C=C, sigma=sigma)
        return DistSpMVPlan(ops, mesh, exchange=exchange)


# ---------------------------------------------------------------------------
# Distributed tier ladder (adaptive_pcg_dist)
# ---------------------------------------------------------------------------


class DistTierLadder(_MeshBound):
    """One member set per codec tier over ONE shared partition — what
    :func:`repro.solvers.cg.adaptive_pcg_dist` promotes through.

    Every tier shares the halo maps and row mask (``dev['shared']``); each
    tier's member arrays + inverse permutations live under
    ``dev['tiers'][k]`` and the exact fp64 operator (the outer
    true-residual recomputation of iterative refinement) under
    ``dev['hi']``. Tier choice inside the solve is a traced ``lax.switch``
    over the per-tier composite bodies; the halo gather is hoisted out of
    the switch as the shared pre-stage (one collective per matvec,
    whatever the tier).
    """

    def __init__(self, tiers_ops: list, hi_ops: DistOperands, mesh, *,
                 labels, sub32, exchange: str = "ppermute"):
        if exchange not in dh.EXCHANGE_MODES:
            raise ValueError(f"exchange={exchange!r} not in "
                             f"{dh.EXCHANGE_MODES}")
        self.tiers = list(tiers_ops)
        self.hi = hi_ops
        self.labels = list(labels)
        self.sub32 = np.asarray(sub32, bool)
        self.exchange = exchange

        def member_only(ops):
            return {k: v for k, v in ops.host.items()
                    if k not in SHARED_KEYS}

        host = {
            "shared": {k: self.tiers[0].host[k] for k in SHARED_KEYS},
            "tiers": [member_only(o) for o in self.tiers],
            "hi": member_only(hi_ops),
        }
        self._bind(self.tiers[0], mesh, host)

    @property
    def h_pad(self) -> int:
        return self.tiers[0].h_pad


def build_dist_tiers(a: sp.csr_matrix, ladder, *, mesh=None,
                     n_shards: int | None = None,
                     axis_name: str = "shards",
                     exchange: str = "ppermute", C: int = 32,
                     sigma: int = 256, devices=None) -> DistTierLadder:
    """Materialize a whole-operator codec ladder (e.g.
    ``precision.select.tier_ladder``) as distributed member sets sharing
    one partition, plus the exact fp64 member set for the refinement
    outer step."""
    if mesh is None:
        mesh = make_shard_mesh(n_shards, axis_name=axis_name,
                               devices=devices)
    ncls = _normalize_classes(ladder)
    a = a.tocsr()
    with _obs.host_span("packsell.dist_build"):
        ctx = _partition_context(a, int(mesh.devices.size), C)
        tiers_ops = [build_composite_operands(
            a, int(mesh.devices.size), classes=[(codec, D, None)],
            C=C, sigma=sigma, ctx=ctx) for codec, D, _ in ncls]
        hi_ops = build_composite_operands(
            a, int(mesh.devices.size), classes=[("fp64", 0, None)],
            C=C, sigma=sigma, ctx=ctx)
        labels = [codec if codec in kc.SELL_CODECS else f"{codec}/D={D}"
                  for codec, D, _ in ncls]
        sub32 = [codec not in kc.SELL_CODECS for codec, D, _ in ncls]
        return DistTierLadder(tiers_ops, hi_ops, mesh, labels=labels,
                              sub32=sub32, exchange=exchange)
