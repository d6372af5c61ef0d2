"""The training driver: mesh + data + step + checkpoints + fault tolerance.

The same Trainer runs the CPU examples (1 device, debug mesh) and the
production configuration (the launcher passes the 16×16 / 2×16×16 mesh);
everything mesh-dependent flows through the logical-spec machinery in
``repro.parallel`` so no code changes between scales.

Gradient compression (beyond-paper application of the paper's E8MY codec,
see ``repro/optim/compression.py``) is wired as an opt-in pure-DP step
built with ``shard_map``: each data shard computes grads locally, truncates
mantissas with error feedback, and psums the narrow payload — the exact
construction that would run on the inter-pod axis at scale.
"""
from __future__ import annotations

import dataclasses
import json
import os
import time
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.data import DataConfig, SyntheticTokenStream
from repro.models import transformer as tfm
from repro.models.config import ModelConfig
from repro.optim import (OptConfig, TrainState, apply_updates, init_state,
                         zero_spec_tree)
from repro.optim.compression import compress
from repro.parallel import shard_map_unchecked, tree_shardings_shaped
from repro.train.checkpoint import CheckpointManager
from repro.train.fault import PreemptionGuard, StepMonitor

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    steps: int = 100
    ckpt_dir: str = "/tmp/repro_ckpt"
    ckpt_every: int = 50
    keep: int = 3
    log_every: int = 10
    seed: int = 0
    # data
    seq_len: int = 256
    global_batch: int = 8
    # distribution
    data_axis: int = 1            # debug-mesh DP size (examples/tests)
    model_axis: int = 1
    # gradient accumulation: microbatch size per step (None = full batch).
    # Halving the microbatch roughly halves activation residency — the
    # knob that fits dbrx-132b train_4k under 16 GB/device (EXPERIMENTS §B)
    microbatch: int | None = None
    # fault tolerance
    straggler_threshold: float = 2.0
    # gradient compression (None = off; int = E8M<bits> mantissa)
    grad_compression: int | None = None


class Trainer:
    def __init__(self, model_cfg: ModelConfig, opt_cfg: OptConfig,
                 tcfg: TrainerConfig, mesh=None,
                 log_fn: Callable[[str], None] = print):
        self.cfg = model_cfg
        self.opt = opt_cfg
        self.tcfg = tcfg
        self.log = log_fn
        self.mesh = mesh or jax.make_mesh(
            (tcfg.data_axis, tcfg.model_axis), ("data", "model"),
            axis_types=(AxisType.Auto,) * 2)
        self.ckpt = CheckpointManager(tcfg.ckpt_dir, keep=tcfg.keep)
        self.monitor = StepMonitor(threshold=tcfg.straggler_threshold)
        self.data = SyntheticTokenStream(DataConfig(
            vocab=model_cfg.vocab, seq_len=tcfg.seq_len,
            global_batch=tcfg.global_batch, seed=tcfg.seed))
        self.history: list[dict] = []
        self._build()

    # ------------------------------------------------------------------
    def _build(self):
        cfg, opt, mesh = self.cfg, self.opt, self.mesh
        shapes, specs = tfm.abstract_params(cfg)
        self.param_specs = specs
        dsize = mesh.shape.get("data", 1)
        self.zspecs = zero_spec_tree(specs, shapes, data_size=dsize)
        self.state_specs = TrainState(P(), self.zspecs, self.zspecs,
                                      self.zspecs)
        if self.tcfg.grad_compression is None:
            step = self._make_pjit_step()
        else:
            step = self._make_compressed_step()
        self._step_fn = step

    def _make_pjit_step(self):
        cfg, opt = self.cfg, self.opt
        specs, zspecs = self.param_specs, self.zspecs
        from repro.parallel import constrain

        def to_compute(master):
            # blocks stay master-typed; the layer scan casts per layer (B4a)
            cdtype = jnp.dtype(cfg.dtype)
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

        mb = self.tcfg.microbatch
        gb = self.tcfg.global_batch
        if mb is not None and (gb % mb != 0 or mb >= gb):
            raise ValueError(f"microbatch {mb} must divide global batch "
                             f"{gb} and be smaller")

        def loss_fn(master, batch):
            return tfm.forward_train(cfg, to_compute(master), batch)

        def train_step(state: TrainState, batch):
            if mb is None:
                loss, grads = jax.value_and_grad(loss_fn)(state.master,
                                                          batch)
            else:
                # gradient accumulation over gb/mb microbatches: activation
                # residency scales with mb, gradients/loss are the exact
                # full-batch mean (each microbatch weighted equally)
                n_micro = gb // mb
                stacked = jax.tree.map(
                    lambda x: x.reshape((n_micro, mb) + x.shape[1:]), batch)

                def acc_step(carry, mbatch):
                    loss_sum, gacc = carry
                    l, g = jax.value_and_grad(loss_fn)(state.master, mbatch)
                    gacc = jax.tree.map(jnp.add, gacc, g)
                    return (loss_sum + l, gacc), None

                zero_g = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), state.master)
                (loss_sum, gsum), _ = jax.lax.scan(
                    acc_step, (jnp.zeros((), jnp.float32), zero_g), stacked)
                loss = loss_sum / n_micro
                grads = jax.tree.map(lambda g: g / n_micro, gsum)
            new_state = apply_updates(state, grads, opt, zero_specs=zspecs)
            return new_state, {"loss": loss}

        return train_step

    def _make_compressed_step(self):
        """Pure-DP step with E8MY-compressed gradient psum (shard_map)."""
        cfg, opt, mesh = self.cfg, self.opt, self.mesh
        bits = self.tcfg.grad_compression

        def shard_step(state, err, batch):
            # params replicated; batch sharded over 'data'
            def loss_fn(master):
                p = jax.tree.map(
                    lambda x: x.astype(jnp.dtype(cfg.dtype)), master)
                return tfm.forward_train(cfg, p, batch)

            loss, grads = jax.value_and_grad(loss_fn)(state.master)
            nshards = jax.lax.psum(1, "data")

            def one(g, e):
                q, e2 = compress(g / nshards, e, bits)
                return jax.lax.psum(q, "data"), e2

            flat_g, treedef = jax.tree.flatten(grads)
            flat_e = jax.tree.leaves(err)
            summed, new_err = [], []
            for g, e in zip(flat_g, flat_e):
                s, e2 = one(g, e)
                summed.append(s)
                new_err.append(e2)
            grads = jax.tree.unflatten(treedef, summed)
            err = jax.tree.unflatten(treedef, new_err)
            loss = jax.lax.pmean(loss, "data")
            new_state = apply_updates(state, grads, opt)
            return new_state, err, {"loss": loss}

        rep = P()
        bspec = P("data")

        def spec_like(tree, spec):
            return jax.tree.map(lambda _: spec, tree)

        def train_step(state, err, batch):
            shapes = jax.tree.map(lambda x: x, state)
            fn = shard_map_unchecked(
                shard_step, mesh,
                in_specs=(spec_like(state, rep), spec_like(err, rep),
                          spec_like(batch, bspec)),
                out_specs=(spec_like(shapes, rep), spec_like(err, rep),
                           {"loss": rep}))
            return fn(state, err, batch)

        return train_step

    # ------------------------------------------------------------------
    def init_or_restore(self) -> TrainState:
        shapes, _ = tfm.abstract_params(self.cfg)
        latest = self.ckpt.latest_step()
        if latest is not None:
            f32 = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32), shapes)
            template = TrainState(
                jax.ShapeDtypeStruct((), jnp.int32), f32,
                jax.tree.map(lambda s: s, f32), jax.tree.map(lambda s: s, f32))
            state, meta = self.ckpt.restore(template, mesh=self.mesh)
            self.data.restore(meta["extra"]["data_state"])
            self.log(f"[trainer] restored step {meta['step']} "
                     f"from {self.tcfg.ckpt_dir}")
            return state
        params = tfm.init_params(self.cfg, jax.random.PRNGKey(
            self.tcfg.seed))[0]
        return init_state(params)

    def _save(self, state: TrainState, step: int):
        info = self.ckpt.save(
            step, state, spec_tree=self.state_specs,
            extra={"data_state": self.data.state(),
                   "model": self.cfg.name})
        self.log(f"[trainer] checkpoint step {step} "
                 f"({info['save_s']:.2f}s) -> {info['path']}")

    # ------------------------------------------------------------------
    def run(self, state: TrainState | None = None) -> TrainState:
        tcfg = self.tcfg
        with self.mesh:
            if state is None:
                state = self.init_or_restore()
            start = int(jax.device_get(state.step))
            jit_step = jax.jit(self._step_fn, donate_argnums=(0,)) \
                if tcfg.grad_compression is None else None
            err = None
            if tcfg.grad_compression is not None:
                err = jax.tree.map(
                    lambda x: jnp.zeros(x.shape, jnp.float32), state.master)
                jit_step = jax.jit(self._step_fn, donate_argnums=(0, 1))

            with PreemptionGuard() as guard:
                for step in range(start, tcfg.steps):
                    self.monitor.start()
                    batch = self.data.next_placed_batch(self.mesh)
                    if tcfg.grad_compression is None:
                        state, metrics = jit_step(state, batch)
                    else:
                        state, err, metrics = jit_step(state, err, batch)
                    loss = float(jax.device_get(metrics["loss"]))
                    ev = self.monitor.stop(step)
                    if ev is not None:
                        self.log(f"[straggler] step {ev.step}: "
                                 f"{ev.step_time:.3f}s = {ev.ratio:.1f}x "
                                 f"EWMA {ev.ewma:.3f}s"
                                 + ("  -> exclusion recommended"
                                    if self.monitor.exclusion_recommended
                                    else ""))
                    rec = {"step": step + 1, "loss": loss}
                    self.history.append(rec)
                    if (step + 1) % tcfg.log_every == 0 or step == start:
                        self.log(f"[train] step {step + 1:5d}  "
                                 f"loss {loss:.4f}")
                    if (step + 1) % tcfg.ckpt_every == 0:
                        self._save(state, step + 1)
                    if guard.fired:
                        self.log("[trainer] preemption signal — saving and "
                                 "exiting cleanly")
                        self._save(state, step + 1)
                        break
        return state

    def dump_history(self, path: str):
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as f:
            json.dump(self.history, f, indent=1)
