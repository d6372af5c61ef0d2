"""System tests: end-to-end trainer, checkpoint/restart, fault tolerance,
data pipeline, serving engine — the substrate layers working together."""
import os
import shutil
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data import DataConfig, SyntheticTokenStream
from repro.models import transformer as tfm
from repro.optim import OptConfig
from repro.serving import DecodeEngine, ServeConfig
from repro.train import (CheckpointManager, PreemptionGuard, StepMonitor,
                         Trainer, TrainerConfig)


def tiny_cfg():
    return configs.reduce(configs.get("qwen2-0.5b"))


# ---------------------------------------------------------------------------
# data pipeline
# ---------------------------------------------------------------------------


class TestData:
    def test_deterministic_across_instances(self):
        cfg = DataConfig(vocab=128, seq_len=32, global_batch=4, seed=7)
        a = SyntheticTokenStream(cfg).next_host_batch()
        b = SyntheticTokenStream(cfg).next_host_batch()
        np.testing.assert_array_equal(a["tokens"], b["tokens"])

    def test_resume_is_exact(self):
        cfg = DataConfig(vocab=128, seq_len=32, global_batch=4, seed=3)
        s1 = SyntheticTokenStream(cfg)
        batches = [s1.next_host_batch() for _ in range(4)]
        s2 = SyntheticTokenStream(cfg)
        s2.restore({"step": 2, "seed": 3})
        np.testing.assert_array_equal(s2.next_host_batch()["tokens"],
                                      batches[2]["tokens"])

    def test_shard_rows_independent(self):
        """Any row range regenerates identically (elastic workers)."""
        cfg = DataConfig(vocab=128, seq_len=16, global_batch=8, seed=1)
        s = SyntheticTokenStream(cfg)
        full = s.batch_rows(5, 0, 8)
        part = s.batch_rows(5, 3, 6)
        np.testing.assert_array_equal(full["tokens"][3:6], part["tokens"])

    def test_labels_are_shifted_tokens(self):
        cfg = DataConfig(vocab=128, seq_len=16, global_batch=2, seed=1)
        b = SyntheticTokenStream(cfg).next_host_batch()
        assert b["tokens"].shape == (2, 16)
        assert (b["labels"][:, :-1] == b["tokens"][:, 1:]).all()

    def test_learnable_signal(self):
        """The Markov structure bounds each token's successor set."""
        cfg = DataConfig(vocab=64, seq_len=256, global_batch=8, seed=0,
                         branch=2, noise=0.0)
        b = SyntheticTokenStream(cfg).next_host_batch()
        succ = {}
        for row in b["tokens"]:
            for t in range(len(row) - 1):
                succ.setdefault(int(row[t]), set()).add(int(row[t + 1]))
        assert max(len(v) for v in succ.values()) <= 2


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


class TestCheckpoint:
    def test_roundtrip_and_keep_k(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=2)
        tree = {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
                "b": {"c": jnp.ones((4,), jnp.int32)}}
        for step in (1, 2, 3):
            mgr.save(step, tree, extra={"data_state": {"step": step,
                                                       "seed": 0}})
        assert mgr.steps() == [2, 3]          # keep-k pruned step 1
        template = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)
        restored, meta = mgr.restore(template)
        assert meta["step"] == 3
        np.testing.assert_array_equal(np.asarray(restored["a"]),
                                      np.asarray(tree["a"]))
        np.testing.assert_array_equal(np.asarray(restored["b"]["c"]),
                                      np.asarray(tree["b"]["c"]))

    def test_no_tmp_dirs_after_commit(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), keep=3)
        mgr.save(1, {"x": jnp.zeros((2,))})
        leftovers = [d for d in os.listdir(tmp_path) if d.startswith(".tmp")]
        assert leftovers == []

    def test_missing_leaf_raises(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"x": jnp.zeros((2,))})
        template = {"x": jax.ShapeDtypeStruct((2,), jnp.float32),
                    "y": jax.ShapeDtypeStruct((2,), jnp.float32)}
        with pytest.raises(KeyError):
            mgr.restore(template)

    def test_shape_mismatch_raises(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path))
        mgr.save(1, {"x": jnp.zeros((2,))})
        with pytest.raises(ValueError):
            mgr.restore({"x": jax.ShapeDtypeStruct((3,), jnp.float32)})

    def test_elastic_mesh_restore(self, tmp_path):
        """Spec-tagged save restores onto a (1,1)-mesh with filtered axes."""
        from jax.sharding import PartitionSpec as P
        mgr = CheckpointManager(str(tmp_path))
        tree = {"w": jnp.arange(32, dtype=jnp.float32).reshape(4, 8)}
        mgr.save(1, tree, spec_tree={"w": P("data", "model")})
        mesh = jax.make_mesh((1, 1), ("data", "model"))
        restored, _ = mgr.restore(
            {"w": jax.ShapeDtypeStruct((4, 8), jnp.float32)}, mesh=mesh)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(tree["w"]))


# ---------------------------------------------------------------------------
# trainer end-to-end
# ---------------------------------------------------------------------------


class TestTrainer:
    def _tcfg(self, tmp, **kw):
        d = dict(steps=4, ckpt_dir=str(tmp), ckpt_every=2, log_every=10,
                 seq_len=32, global_batch=2)
        d.update(kw)
        return TrainerConfig(**d)

    def test_train_checkpoint_resume(self, tmp_path):
        cfg = tiny_cfg()
        opt = OptConfig(warmup=1, total_steps=4)
        t1 = Trainer(cfg, opt, self._tcfg(tmp_path), log_fn=lambda s: None)
        s1 = t1.run()
        assert int(jax.device_get(s1.step)) == 4
        assert t1.ckpt.steps() == [2, 4]
        # resume: restores step 4, no further steps executed
        t2 = Trainer(cfg, opt, self._tcfg(tmp_path), log_fn=lambda s: None)
        s2 = t2.run()
        assert int(jax.device_get(s2.step)) == 4
        for a, b in zip(jax.tree.leaves(s1.master),
                        jax.tree.leaves(s2.master)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b))

    def test_losses_finite_and_stable(self, tmp_path):
        cfg = tiny_cfg()
        tcfg = self._tcfg(tmp_path, steps=8, ckpt_every=100)
        t = Trainer(cfg, OptConfig(lr_peak=3e-3, warmup=2, total_steps=8),
                    tcfg, log_fn=lambda s: None)
        t.run()
        losses = [h["loss"] for h in t.history]
        assert all(np.isfinite(l) for l in losses)
        assert losses[-1] < losses[0] + 0.1   # not diverging

    def test_microbatch_matches_full_batch(self, tmp_path):
        """Gradient accumulation = exact full-batch mean: same losses."""
        cfg = tiny_cfg()
        opt = OptConfig(warmup=1, total_steps=3)
        t_full = Trainer(cfg, opt, self._tcfg(
            tmp_path / "a", steps=3, ckpt_every=100, global_batch=4),
            log_fn=lambda s: None)
        t_full.run()
        t_micro = Trainer(cfg, opt, self._tcfg(
            tmp_path / "b", steps=3, ckpt_every=100, global_batch=4,
            microbatch=2), log_fn=lambda s: None)
        t_micro.run()
        for a, b in zip(t_full.history, t_micro.history):
            np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-4)

    def test_grad_compression_path(self, tmp_path):
        cfg = tiny_cfg()
        tcfg = self._tcfg(tmp_path, steps=2, ckpt_every=100,
                          grad_compression=10)
        t = Trainer(cfg, OptConfig(warmup=1, total_steps=2), tcfg,
                    log_fn=lambda s: None)
        t.run()
        assert len(t.history) == 2
        assert all(np.isfinite(h["loss"]) for h in t.history)


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------


class TestFault:
    def test_preemption_guard_catches_sigterm(self):
        with PreemptionGuard() as guard:
            os.kill(os.getpid(), signal.SIGTERM)
            assert guard.fired

    def test_preemption_guard_restores_handler(self):
        prev = signal.getsignal(signal.SIGTERM)
        with PreemptionGuard():
            pass
        assert signal.getsignal(signal.SIGTERM) is prev

    def test_straggler_detection(self):
        mon = StepMonitor(alpha=0.5, threshold=1.5, trip_limit=2, warmup=0)
        # feed synthetic step times through the monitor's clock
        times = iter([0.0, 0.1,    # step 0 (0.1s, sets EWMA)
                      0.2, 0.3,    # step 1 (0.1s)
                      0.4, 0.9,    # step 2 (0.5s -> straggler)
                      1.0, 1.6])   # step 3 (0.6s -> straggler)
        import repro.train.fault as fault
        orig = fault.time.perf_counter
        fault.time.perf_counter = lambda: next(times)
        try:
            events = []
            for i in range(4):
                mon.start()
                ev = mon.stop(i)
                if ev:
                    events.append(ev)
            assert len(events) == 2
            assert mon.exclusion_recommended
        finally:
            fault.time.perf_counter = orig


# ---------------------------------------------------------------------------
# serving engine
# ---------------------------------------------------------------------------


class TestServing:
    @pytest.fixture(scope="class")
    def engine_setup(self):
        cfg = tiny_cfg()
        params, _ = tfm.init_params(cfg, jax.random.PRNGKey(0))
        return cfg, params

    def test_continuous_batching_drains_queue(self, engine_setup):
        cfg, params = engine_setup
        eng = DecodeEngine(cfg, params, ServeConfig(slots=2, max_len=48))
        rng = np.random.default_rng(0)
        reqs = [eng.submit(rng.integers(1, cfg.vocab, size=5),
                           max_new_tokens=4) for _ in range(5)]
        done = eng.run()
        assert len(done) == 5
        for r in reqs:
            assert len(r.out_tokens) == 4
            assert r.t_done >= r.t_first >= r.t_submit

    def test_greedy_matches_manual_decode(self, engine_setup):
        """Engine greedy decode == prefill + manual forward_decode chain."""
        cfg, params = engine_setup
        prompt = np.arange(1, 7, dtype=np.int32)
        eng = DecodeEngine(cfg, params, ServeConfig(slots=1, max_len=32))
        eng.submit(prompt, max_new_tokens=3)
        done = eng.run()
        got = done[0].out_tokens

        logits, cache = jax.jit(
            lambda p, b: tfm.forward_prefill(cfg, p, b, 32))(
                params, {"tokens": jnp.asarray(prompt[None, :])})
        want = [int(jnp.argmax(logits[0, -1]))]
        tok = jnp.asarray([[want[0]]], jnp.int32)
        for _ in range(2):
            logits, cache = jax.jit(
                lambda p, t, c: tfm.forward_decode(cfg, p, t, c))(
                    params, tok, cache)
            nxt = int(jnp.argmax(logits[0, -1]))
            want.append(nxt)
            tok = jnp.asarray([[nxt]], jnp.int32)
        assert got == want

    def test_warmup_with_precision_store(self, engine_setup, tmp_path,
                                         caplog):
        """warmup(precision_store=...) logs auto-selected layer codecs and
        restores (sb, wb) retile winners into the layer plans."""
        import logging

        from repro.models.sparse_linear import PackSELLLinear
        from repro.precision import PrecisionStore

        cfg, params = engine_setup
        eng = DecodeEngine(cfg, params, ServeConfig(slots=1, max_len=32))
        w = np.random.default_rng(0).standard_normal((48, 32)) \
            .astype(np.float32)
        path = str(tmp_path / "prec.json")
        lin = PackSELLLinear.from_dense(w, density=0.4, codec="auto",
                                        error_budget=1e-3, store=path,
                                        C=8, sigma=32)
        st = PrecisionStore(path)
        tiles = [(4, 16)] * len(lin.plan.tiles)
        st.put_retile(lin.fingerprint,
                      f"plan_{lin.mat.codec_name}{lin.mat.D}", tiles)
        with caplog.at_level(logging.INFO, logger="repro.serving.engine"):
            eng.warmup(sparse_layers=[lin], precision_store=path)
        msgs = " ".join(r.getMessage() for r in caplog.records)
        assert "auto-selected" in msgs
        assert "retiled from store" in msgs
        assert lin.plan.tiles == tuple(tiles)

    def test_eos_terminates(self, engine_setup):
        cfg, params = engine_setup
        # find the first greedy token, then make it the EOS
        eng0 = DecodeEngine(cfg, params, ServeConfig(slots=1, max_len=32))
        eng0.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=1)
        first = eng0.run()[0].out_tokens[0]
        eng = DecodeEngine(cfg, params,
                           ServeConfig(slots=1, max_len=32, eos_id=first))
        req = eng.submit(np.arange(1, 5, dtype=np.int32), max_new_tokens=8)
        eng.run()
        assert req.out_tokens[-1] == first
        assert len(req.out_tokens) == 1


# ---------------------------------------------------------------------------
# launch: compile cache, device peaks
# ---------------------------------------------------------------------------


class TestLaunch:
    @pytest.fixture
    def cache_config(self):
        prev = jax.config.jax_compilation_cache_dir
        keyed = jax.config.jax_compilation_cache_include_metadata_in_key
        yield
        jax.config.update("jax_compilation_cache_dir", prev)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          keyed)

    def test_compile_cache_defaults_to_checkout(self, cache_config,
                                                monkeypatch):
        from repro.launch import compile_cache as cc
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        path = cc.use_compile_cache()
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert path == os.path.join(root, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()

    def test_compile_cache_env_wins(self, cache_config, monkeypatch,
                                    tmp_path):
        from repro.launch import compile_cache as cc
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert cc.use_compile_cache() == str(tmp_path)
        # JAX reads the variable itself: no other directory is set in code
        assert jax.config.jax_compilation_cache_dir is None
        assert jax.config.jax_compilation_cache_include_metadata_in_key

    def test_peak_bandwidth_by_device_kind(self):
        from repro.launch import roofline as rl
        peak = rl.peak_bandwidth("TPU v5 lite")
        assert peak["bw_bytes_per_s"] == 819e9 and "v5e" in peak["source"]
        with pytest.raises(KeyError, match="no published HBM peak"):
            rl.peak_bandwidth("cpu")
