"""End-to-end training driver: synthetic-language LM with the full stack —
data pipeline, AdamW+ZeRO specs, checkpointing, straggler monitor,
preemption-safe loop, optional E8MY gradient compression.

    PYTHONPATH=src python examples/train_lm.py --preset smoke
    PYTHONPATH=src python examples/train_lm.py --preset full      # ~100M
    PYTHONPATH=src python examples/train_lm.py --preset smoke \
        --grad-compression 10                                     # E8M10 DP

The synthetic data is an order-1 Markov language (repro/data): uniform
entropy is ln(vocab); a model that learns the table approaches the
mixture floor, so the loss curve is a real learning signal, asserted at
the end.
"""
import argparse
import math
import shutil

import jax

from repro import configs
from repro.launch.compile_cache import use_compile_cache
from repro.models.config import ModelConfig
from repro.optim import OptConfig
from repro.train import Trainer, TrainerConfig

PRESETS = {
    # ~1.6M params, < 2 min on 1 CPU
    "smoke": dict(
        model=dict(name="lm-smoke", family="dense", n_layers=2, d_model=128,
                   n_heads=4, n_kv_heads=2, d_ff=256, vocab=512,
                   dtype="float32"),
        steps=30, seq_len=128, global_batch=4, ckpt_every=15,
    ),
    # ~100M params — the assignment's end-to-end driver size
    "full": dict(
        model=dict(name="lm-100m", family="dense", n_layers=12, d_model=512,
                   n_heads=8, n_kv_heads=4, d_ff=2560, vocab=32_768,
                   dtype="float32"),
        steps=200, seq_len=512, global_batch=8, ckpt_every=50,
    ),
}


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="smoke", choices=list(PRESETS))
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--resume", action="store_true",
                    help="keep existing checkpoints (restart test)")
    ap.add_argument("--grad-compression", type=int, default=None,
                    help="E8M<bits> gradient compression on the DP axis")
    args = ap.parse_args()

    p = PRESETS[args.preset]
    cfg = ModelConfig(**p["model"])
    steps = args.steps or p["steps"]
    if not args.resume:
        shutil.rmtree(args.ckpt_dir, ignore_errors=True)

    print(f"model: {cfg.name}  params ~{cfg.param_count() / 1e6:.1f}M  "
          f"steps {steps}")
    tcfg = TrainerConfig(
        steps=steps, ckpt_dir=args.ckpt_dir, ckpt_every=p["ckpt_every"],
        log_every=max(steps // 20, 1), seq_len=p["seq_len"],
        global_batch=p["global_batch"],
        grad_compression=args.grad_compression)
    opt = OptConfig(lr_peak=3e-3, warmup=max(steps // 10, 1),
                    total_steps=steps)
    trainer = Trainer(cfg, opt, tcfg)
    trainer.run()

    losses = [h["loss"] for h in trainer.history]
    uniform = math.log(cfg.vocab)
    print(f"\nloss: first {losses[0]:.3f} -> last {losses[-1]:.3f} "
          f"(uniform entropy {uniform:.3f})")
    assert losses[-1] < losses[0] - 0.2, "no learning signal!"
    print("learning-signal assertion passed; checkpoints:",
          trainer.ckpt.steps())


if __name__ == "__main__":
    main()
