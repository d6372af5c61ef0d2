"""Mesh-aware sharding helpers.

All model code expresses layouts with *logical* PartitionSpecs over axis
names {"pod", "data", "model"}. ``constrain`` applies a sharding constraint
only when a mesh with those axes is active (no-op on a single device, so
smoke tests and the quickstart run unchanged), and ``filter_spec`` adapts
specs to whichever mesh (single- or multi-pod) is in scope.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def current_mesh() -> Mesh | None:
    try:
        m = jax._src.mesh.thread_resources.env.physical_mesh
        if m is None or m.empty:
            return None
        return m
    except Exception:
        return None


def batch_axes(mesh: Mesh | None = None):
    """The data-parallel axes: ('pod','data') on multi-pod, ('data',) else."""
    mesh = mesh or current_mesh()
    if mesh is None:
        return ()
    ax = [a for a in ("pod", "data") if a in mesh.axis_names]
    return tuple(ax)


def filter_spec(spec: P, mesh: Mesh | None = None) -> P:
    """Drop axis names not present in the mesh (adapts to any mesh shape)."""
    mesh = mesh or current_mesh()
    names = set(mesh.axis_names) if mesh is not None else set()

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(a for a in entry if a in names)
            return kept if kept else None
        return entry if entry in names else None

    return P(*(keep(e) for e in spec))


def _manual_axes() -> frozenset:
    """Axis names currently under shard_map manual control."""
    return frozenset(jax.sharding.get_abstract_mesh().manual_axes)


def _in_manual_context() -> bool:
    return bool(_manual_axes())


def constrain(x, spec: P):
    """with_sharding_constraint that degrades gracefully: no-op without a
    mesh (single-device tests); inside a shard_map manual region, manual
    axis names are dropped from the spec (constraints on the remaining
    auto axes still apply — partial-manual pod steps keep the TP/SP
    layout); if nothing remains, the constraint is skipped entirely."""
    mesh = current_mesh()
    if mesh is None:
        return x
    manual = _manual_axes()
    fs = filter_spec(spec, mesh)
    if manual:
        def drop(e):
            if e is None:
                return None
            names = e if isinstance(e, (tuple, list)) else (e,)
            kept = tuple(a for a in names if a not in manual)
            return kept if kept else None

        fs = P(*(drop(e) for e in fs))
        if all(e is None for e in fs):
            return x
        # inside shard_map the constraint must be expressed against the
        # context (abstract) mesh — pass the raw PartitionSpec
        return jax.lax.with_sharding_constraint(x, fs)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, fs))


def shard_map_unchecked(f, mesh: Mesh, in_specs, out_specs,
                        axis_names=None):
    """``jax.shard_map`` with replication checking disabled
    (``check_vma=False``). ``axis_names`` restricts the manually-mapped
    axes."""
    kw = {"check_vma": False}
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kw)


def make_shard_mesh(n_shards: int | None = None, *,
                    axis_name: str = "shards", devices=None) -> Mesh:
    """1-D mesh over (the first ``n_shards``) local devices — the device
    axis the distributed PackSELL layer partitions matrices across
    (``repro.distributed``). Defaults to every visible device."""
    devs = list(devices) if devices is not None else jax.devices()
    if n_shards is not None:
        if n_shards > len(devs):
            raise ValueError(f"n_shards={n_shards} > {len(devs)} devices "
                             "(run under XLA_FLAGS="
                             "--xla_force_host_platform_device_count=N)")
        devs = devs[:n_shards]
    return Mesh(np.array(devs), (axis_name,))


def constrain_batch(x):
    """Shard the leading (batch) dim over the DP axes."""
    mesh = current_mesh()
    if mesh is None or _in_manual_context():
        return x
    ax = batch_axes(mesh)
    if not ax:
        return x
    spec = P(ax, *(None,) * (x.ndim - 1))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(mesh: Mesh, spec: P) -> NamedSharding:
    return NamedSharding(mesh, filter_spec(spec, mesh))


def tree_shardings(mesh: Mesh, spec_tree):
    return jax.tree.map(lambda s: named_sharding(mesh, s), spec_tree,
                        is_leaf=lambda s: isinstance(s, P))


def _axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def sanitize_spec(spec: P, shape, mesh: Mesh) -> P:
    """Drop sharding on dims the mesh cannot divide evenly (jit argument
    shardings require exact divisibility, e.g. batch=1 long-context decode)."""
    sizes = _axis_sizes(mesh)
    spec = filter_spec(spec, mesh)
    entries = []
    for i, e in enumerate(spec):
        if e is None or i >= len(shape):
            entries.append(None if i >= len(shape) else e)
            continue
        names = e if isinstance(e, (tuple, list)) else (e,)
        prod = 1
        for nm in names:
            prod *= sizes.get(nm, 1)
        entries.append(e if prod and shape[i] % prod == 0 else None)
    return P(*entries)


def tree_shardings_shaped(mesh: Mesh, spec_tree, shape_tree):
    """NamedShardings with per-leaf divisibility sanitation."""
    spec_leaves, treedef = jax.tree.flatten(
        spec_tree, is_leaf=lambda s: isinstance(s, P))
    shape_leaves = jax.tree.leaves(shape_tree)
    assert len(spec_leaves) == len(shape_leaves), \
        (len(spec_leaves), len(shape_leaves))
    out = [NamedSharding(mesh, sanitize_spec(s, sh.shape, mesh))
           for s, sh in zip(spec_leaves, shape_leaves)]
    return jax.tree.unflatten(treedef, out)
