"""Halo exchange for distributed PackSELL SpMV (DESIGN.md §7.2).

Before ``y_p = A_loc @ x_loc + A_rem @ x_halo`` can run, each shard must
receive the x-entries its halo columns reference. Two exchange modes, both
driven entirely by **precomputed index maps** (host-built once per
partition, no device-side set logic):

* ``'ppermute'`` (default, the Kreutzer-et-al. recipe): P-1 rounds of
  ``jax.lax.ppermute``. In round s every shard packs the entries shard
  ``(p+s) % P`` needs from it (``send_idx``), the ring rotates by s, and the
  receiver scatters the buffer into its halo slots (``recv_slot``). Only
  owned entries that some neighbor actually needs ever move; buffers are
  padded to the fleet-wide per-pair maximum ``k_max`` so every round is one
  static-shape collective.
* ``'all_gather'``: one ``jax.lax.all_gather`` of the full x-block followed
  by a gather through ``halo_src``. Simpler, more traffic — the baseline the
  benchmarks compare against.

Sender and receiver agree on buffer order by construction: both sides
enumerate the pair's columns in sorted-global-column order.

The maps are plain stacked arrays ([P, ...] along the mesh axis) so they
flow through ``shard_map`` in_specs like any other operand; padding entries
send slot 0 (harmless read) and land on slot ``h_pad`` (dropped by the
out-of-bounds scatter mode).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.observe import metrics as _obs

from .partition import RowPartition, comm_counts

EXCHANGE_MODES = ("ppermute", "all_gather")


@dataclasses.dataclass(frozen=True)
class HaloMaps:
    """Host-built exchange index maps, stacked over shards (leading dim P).

    ``halo_src[p, k]``: flattened index into the all-gathered ``[P * n_pad]``
    x of shard p's k-th halo entry (pad → 0).
    ``send_idx[p, s-1, k]``: local x index of the k-th entry shard p sends in
    round s (pad → 0).
    ``recv_slot[p, s-1, k]``: halo slot filled by the k-th entry shard p
    receives in round s (pad → h_pad, dropped).
    """

    n_shards: int
    n_pad: int
    h_pad: int
    k_max: int
    halo_src: np.ndarray        # int32 [P, max(h_pad, 1)]
    send_idx: np.ndarray        # int32 [P, max(P-1, 1), max(k_max, 1)]
    recv_slot: np.ndarray       # int32 [P, max(P-1, 1), max(k_max, 1)]
    counts: np.ndarray          # int64 [P, P] traffic matrix


def build_halo_maps(part: RowPartition, halo_cols_list: list[np.ndarray],
                    *, n_pad: int, h_pad: int) -> HaloMaps:
    """Precompute both modes' index maps from the per-shard halo column
    sets (``ShardSplit.halo_cols``, sorted global ids)."""
    P = part.n_shards
    owners = [part.owner(hc) for hc in halo_cols_list]
    counts = comm_counts(part, halo_cols_list)
    k_max = int(counts.max(initial=0))

    halo_src = np.zeros((P, max(h_pad, 1)), np.int32)
    for p, hc in enumerate(halo_cols_list):
        own = owners[p]
        halo_src[p, :len(hc)] = (own * n_pad
                                 + (hc - part.starts[own])).astype(np.int32)

    n_steps = max(P - 1, 1)
    send_idx = np.zeros((P, n_steps, max(k_max, 1)), np.int32)
    recv_slot = np.full((P, n_steps, max(k_max, 1)), h_pad, np.int32)
    for s in range(1, P):
        for p in range(P):
            dst = (p + s) % P
            # entries dst needs from p, in dst's sorted-halo order
            need = halo_cols_list[dst][owners[dst] == p]
            send_idx[p, s - 1, :len(need)] = \
                (need - part.starts[p]).astype(np.int32)
            src = (p - s) % P
            slots = np.nonzero(owners[p] == src)[0]
            recv_slot[p, s - 1, :len(slots)] = slots.astype(np.int32)
    return HaloMaps(n_shards=P, n_pad=n_pad, h_pad=h_pad, k_max=k_max,
                    halo_src=halo_src, send_idx=send_idx,
                    recv_slot=recv_slot, counts=counts)


def gather_halo(x_loc: jnp.ndarray, dev: dict, *, axis_name: str,
                n_shards: int, h_pad: int, mode: str) -> jnp.ndarray:
    """Device-side exchange (runs inside a shard_map body). ``x_loc`` is
    this shard's ``[n_pad]`` (or ``[n_pad, nb]``) x-block; ``dev`` holds this
    shard's slices of the stacked maps. Returns ``x_halo`` ``[h_pad(, nb)]``.
    The whole exchange (the send takes, the ``ppermute`` rounds and the
    receive scatters, or the ``all_gather`` and its take) sits under the
    device scope ``packsell.halo``.
    """
    out_shape = (h_pad,) + tuple(x_loc.shape[1:])
    if h_pad == 0:
        return jnp.zeros(out_shape, x_loc.dtype)
    if mode not in EXCHANGE_MODES:
        raise ValueError(f"mode={mode!r} not in {EXCHANGE_MODES}")
    with _obs.span("packsell.halo"):
        if mode == "all_gather":
            x_full = jax.lax.all_gather(x_loc, axis_name)  # [P, n_pad(,nb)]
            x_full = x_full.reshape((-1,) + tuple(x_loc.shape[1:]))
            return jnp.take(x_full, dev["halo_src"][:h_pad], axis=0)
        x_halo = jnp.zeros(out_shape, x_loc.dtype)
        for s in range(1, n_shards):
            buf = jnp.take(x_loc, dev["send_idx"][s - 1], axis=0)
            buf = jax.lax.ppermute(
                buf, axis_name,
                perm=[(p, (p + s) % n_shards) for p in range(n_shards)])
            # pad entries carry recv_slot == h_pad -> dropped (out of bounds)
            x_halo = x_halo.at[dev["recv_slot"][s - 1]].set(buf, mode="drop")
        return x_halo


def prestage(shared: dict, *, axis_name: str, n_shards: int, h_pad: int,
             mode: str):
    """The halo exchange packaged as a **composite pre-stage**
    (DESIGN.md §9.2): a function mapping a shard's local x-block to the
    tuple of extra input vectors — ``(x_halo,)``, or ``()`` for halo-free
    partitions — that remote composite members consume as input index 1.

    Hoisting the exchange into a pre-stage is what lets the distributed
    tier ladder (``cg.adaptive_pcg_dist``) run ONE collective per matvec
    outside the tier ``lax.switch``: every tier shares the same index
    maps, so the gathered buffer feeds whichever tier is active. The
    exchange is :func:`gather_halo`, so it carries that function's
    ``packsell.halo`` scope, as the halo of every other distributed
    matvec does.
    """
    def pre(x_loc: jnp.ndarray) -> tuple:
        if h_pad == 0:
            return ()
        return (gather_halo(x_loc, shared, axis_name=axis_name,
                            n_shards=n_shards, h_pad=h_pad, mode=mode),)
    return pre


def gather_halo_reference(x_stacked: np.ndarray, maps: HaloMaps,
                          mode: str = "all_gather") -> np.ndarray:
    """Host-side oracle of :func:`gather_halo` over the full stacked x
    ``[P, n_pad(, nb)]`` → ``[P, h_pad(, nb)]`` (device-free tests)."""
    P, h_pad = maps.n_shards, maps.h_pad
    out_shape = (P, h_pad) + tuple(x_stacked.shape[2:])
    out = np.zeros(out_shape, x_stacked.dtype)
    if h_pad == 0:
        return out
    if mode == "all_gather":
        flat = x_stacked.reshape((-1,) + tuple(x_stacked.shape[2:]))
        for p in range(P):
            out[p] = flat[maps.halo_src[p, :h_pad]]
        return out
    for s in range(1, P):
        for p in range(P):
            src = (p - s) % P
            buf = x_stacked[src][maps.send_idx[src, s - 1]]
            slots = maps.recv_slot[p, s - 1]
            ok = slots < h_pad
            out[p][slots[ok]] = buf[ok]
    return out
