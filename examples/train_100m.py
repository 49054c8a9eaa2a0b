"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps.

    PYTHONPATH=src python examples/train_100m.py --steps 300

Uses the full production stack on one host: config system → data pipeline
(deterministic, restart-safe) → grad-accum train step → AdamW+cosine →
async checkpointing → per-stream telemetry.  Resumable: re-running the same
command continues from the last committed checkpoint.

The model is the mamba2-130m architecture at its published shape (0.13B
params — the '~100M' end-to-end target); pass ``--small`` for a quick CPU
run at reduced width.
"""

import argparse
import sys

sys.path.insert(0, "src")

from dataclasses import replace

import jax

from repro.ckpt.checkpoint import CheckpointManager
from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, make_train_iter
from repro.optim import AdamWConfig, ScheduleConfig
from repro.train.trainer import TrainConfig, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="artifacts/train_100m_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--small", action="store_true", help="reduced width for quick CPU runs")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_smoke_config("mamba2-130m") if args.small else get_config("mamba2-130m")
    if not args.small:
        cfg = replace(cfg, compute_dtype="float32")  # CPU host run
    tcfg = TrainConfig(
        adamw=AdamWConfig(weight_decay=0.1, grad_clip=1.0),
        schedule=ScheduleConfig(peak_lr=6e-4, warmup_steps=20, decay_steps=args.steps),
        microbatches=2,
    )
    dcfg = DataConfig(global_batch=args.batch, seq_len=args.seq, vocab_size=cfg.vocab_size)

    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    trainer = Trainer(cfg, tcfg, make_train_iter(dcfg), ckpt_manager=ckpt,
                      ckpt_every=args.ckpt_every)
    params, opt = trainer.restore_or_init()
    if trainer.step:
        print(f"resumed from checkpoint at step {trainer.step}")
        trainer.data_iter.close()
        trainer.data_iter = make_train_iter(dcfg, start_index=trainer.step)

    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"training {cfg.name}: {n_params/1e6:.1f}M params, "
          f"batch={args.batch}x{args.seq}, {args.steps} steps")

    remaining = max(0, args.steps - trainer.step)
    params, opt, hist = trainer.run(params, opt, remaining)
    ckpt.wait()

    if hist:
        k = max(1, len(hist) // 10)
        first = sum(h["loss"] for h in hist[:k]) / k
        last = sum(h["loss"] for h in hist[-k:]) / k
        print(f"\nloss: first-{k}-avg={first:.4f} → last-{k}-avg={last:.4f}")
    print("\nper-stream summary:")
    trainer.stats.print_summary()
    trainer.data_iter.close()


if __name__ == "__main__":
    main()
