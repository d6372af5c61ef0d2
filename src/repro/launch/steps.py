"""Jittable train / prefill / decode steps with full sharding annotations.

These are the functions the launcher jits for real runs and the dry-run
lowers with ShapeDtypeStructs; one definition serves both.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.optim import OptConfig, TrainState, apply_updates, zero_spec_tree
from repro.parallel import constrain, filter_spec

PyTree = Any


def batch_spec_tree(batch_tree):
    """Shard every batch leaf's leading dim over the DP axes."""
    def spec(leaf):
        nd = len(leaf.shape)
        return P(("pod", "data"), *(None,) * (nd - 1))

    return jax.tree.map(spec, batch_tree)


def cache_spec_tree(cfg: ModelConfig, cache_tree):
    """KV caches: batch over DP axes; KV-head axis over model when the head
    count divides 16, otherwise the head_dim axis (GQA models with few KV
    heads). SSM states: batch over DP, heads/channels over model."""
    kv_on_heads = cfg.n_kv_heads % 16 == 0

    def spec(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = len(leaf.shape)
        if name in ("k", "v", "ek", "ev"):
            # [L, B, S, KV, hd]
            if kv_on_heads:
                return P(None, ("pod", "data"), None, "model", None)
            return P(None, ("pod", "data"), None, None, "model")
        if name == "conv":
            # [L, B, K-1, ch]
            return P(None, ("pod", "data"), None, "model")
        if name == "ssm":
            # [L, B, H, N, P]
            return P(None, ("pod", "data"), "model", None, None)
        if name == "len":
            return P(("pod", "data"))
        return P(*(None,) * nd)

    return jax.tree_util.tree_map_with_path(spec, cache_tree)


def make_train_step(cfg: ModelConfig, opt: OptConfig,
                    pod_wire: str | None = None,
                    microbatch: int | None = None):
    """Returns (train_step, param_specs, zero_specs). State: fp32 master/m/v,
    sharded model×data; compute params materialized in cfg.dtype per step.

    ``pod_wire`` ('u16'|'u8', §Perf C): run the step per pod (shard_map
    manual over the 'pod' axis only) and reduce gradients across pods with
    the integer-wire compressed reduction — the paper's bit-packing idea
    applied to the inter-pod DCI link. Requires the multi-pod mesh.
    """
    shapes, specs = tfm.abstract_params(cfg)
    zspecs = zero_spec_tree(specs, shapes)
    cdtype = jnp.dtype(cfg.dtype)

    def to_compute(master):
        # stacked layer params stay in master dtype/sharding; the layer scan
        # casts one layer at a time (§Perf B4a), so the full compute-param
        # stack never materializes
        out = {}
        for key, sub in master.items():
            if key in ("blocks", "enc_blocks"):
                out[key] = sub
                continue
            leaves, treedef = jax.tree.flatten(sub)
            sp_leaves = jax.tree.flatten(
                specs[key], is_leaf=lambda s: isinstance(s, P))[0]
            out[key] = jax.tree.unflatten(
                treedef, [constrain(x.astype(cdtype), sp)
                          for x, sp in zip(leaves, sp_leaves)])
        return out

    def loss_fn(master, batch):
        params = to_compute(master)   # all-gather over 'data' (ZeRO)
        return tfm.forward_train(cfg, params, batch)

    def grads_of(master, batch):
        if microbatch is None:
            return jax.value_and_grad(loss_fn)(master, batch)
        # gradient accumulation (activation residency ∝ microbatch size);
        # the stacked layout is pinned so the loop dim is replicated and
        # each microbatch keeps the DP sharding (otherwise the reshape of
        # the DP-sharded batch dim confuses the SPMD partitioner)
        gb = jax.tree.leaves(batch)[0].shape[0]
        n_micro = gb // microbatch
        stacked = jax.tree.map(
            lambda x: constrain(
                x.reshape((n_micro, microbatch) + x.shape[1:]),
                P(None, ("pod", "data"), *([None] * (x.ndim - 1)))),
            batch)

        def acc(carry, mb):
            ls, gs = carry
            l, g = jax.value_and_grad(loss_fn)(master, mb)
            return (ls + l, jax.tree.map(jnp.add, gs, g)), None

        zero_g = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                              master)
        (ls, gs), _ = jax.lax.scan(acc, (jnp.zeros((), jnp.float32), zero_g),
                                   stacked)
        return ls / n_micro, jax.tree.map(lambda g: g / n_micro, gs)

    def train_step(state: TrainState, batch) -> tuple[TrainState, dict]:
        loss, grads = grads_of(state.master, batch)
        new_state = apply_updates(state, grads, opt, zero_specs=zspecs)
        return new_state, {"loss": loss}

    if pod_wire is None:
        return train_step, specs, zspecs

    from repro.optim.compression import compressed_wire_reduce
    from repro.parallel import current_mesh, shard_map_unchecked

    def constrain_tree(tree, spec_tree):
        leaves, treedef = jax.tree.flatten(tree)
        sp = jax.tree.flatten(spec_tree,
                              is_leaf=lambda s: isinstance(s, P))[0]
        return jax.tree.unflatten(
            treedef, [constrain(x, s) for x, s in zip(leaves, sp)])

    def pod_body(state: TrainState, batch):
        # the shard_map boundary (in_specs only name the manual 'pod' axis)
        # drops the auto-axes layout — re-pin the ZeRO sharding or GSPMD
        # re-gathers the fp32 master per layer (measured: 90 GB/device)
        state = TrainState(state.step,
                           constrain_tree(state.master, zspecs),
                           constrain_tree(state.m, zspecs),
                           constrain_tree(state.v, zspecs))
        batch = jax.tree.map(
            lambda b: constrain(b, P(("data",), *([None] * (b.ndim - 1)))),
            batch)
        loss, grads = jax.value_and_grad(loss_fn)(state.master, batch)
        grads = constrain_tree(grads, zspecs)
        grads = jax.tree.map(
            lambda g: compressed_wire_reduce(g, "pod", 2, wire=pod_wire),
            grads)
        grads = constrain_tree(grads, zspecs)
        loss = jax.lax.pmean(loss, "pod")
        new_state = apply_updates(state, grads, opt, zero_specs=zspecs)
        new_state = TrainState(new_state.step,
                               constrain_tree(new_state.master, zspecs),
                               constrain_tree(new_state.m, zspecs),
                               constrain_tree(new_state.v, zspecs))
        return new_state, {"loss": loss}

    def train_step_pod(state: TrainState, batch):
        mesh = current_mesh()
        rep = jax.tree.map(lambda _: P(), state)
        bspec = jax.tree.map(lambda _: P("pod"), batch)
        fn = shard_map_unchecked(pod_body, mesh,
                                 in_specs=(rep, bspec),
                                 out_specs=(rep, {"loss": P()}),
                                 axis_names={"pod"})
        return fn(state, batch)

    return train_step_pod, specs, zspecs


def make_prefill_step(cfg: ModelConfig, max_len: int):
    specs = tfm.param_specs(cfg)

    def prefill_step(params, batch):
        return tfm.forward_prefill(cfg, params, batch, max_len)

    return prefill_step, specs


def make_decode_step(cfg: ModelConfig):
    """serve_step: one new token against an existing KV cache."""
    specs = tfm.param_specs(cfg)

    def decode_step(params, tokens, cache):
        logits, new_cache = tfm.forward_decode(cfg, params, tokens, cache)
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        return next_tok[:, None], new_cache

    return decode_step, specs
