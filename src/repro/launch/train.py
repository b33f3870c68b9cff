"""Training CLI: ``python -m repro.launch.train --arch <id> [--reduced] ...``

Examples:
  # CPU-scale run of a reduced config (any assigned arch):
  PYTHONPATH=src python -m repro.launch.train --arch olmoe-1b-7b --reduced \
      --steps 20 --batch 4 --seq 64

  # with checkpointing + injected failure to demonstrate restart:
  PYTHONPATH=src python -m repro.launch.train --arch chatglm3-6b --reduced \
      --steps 30 --ckpt-dir /tmp/ck --fail-at 12
"""

from __future__ import annotations

import argparse

from repro.ckpt.checkpoint import CheckpointConfig
from repro.configs import get_config
from repro.data.pipeline import DataConfig
from repro.launch.cache import enable_compile_cache
from repro.runtime.fault import FailureInjector
from repro.train.optimizer import OptimizerConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()

    trainer = Trainer(
        model_cfg=cfg,
        data_cfg=DataConfig(global_batch=args.batch, seq_len=args.seq),
        opt_cfg=OptimizerConfig(lr=args.lr, total_steps=args.steps, warmup_steps=max(args.steps // 10, 1)),
        trainer_cfg=TrainerConfig(
            total_steps=args.steps,
            ckpt_every=args.ckpt_every,
            microbatches=args.microbatches,
        ),
        ckpt_cfg=CheckpointConfig(args.ckpt_dir) if args.ckpt_dir else None,
        failure_injector=FailureInjector(fail_at_steps=args.fail_at),
    )
    out = trainer.run()
    print(f"final: {out['final_metrics']}")
    print(f"DP gradient all-reduce algorithm chosen by PCCL: "
          f"{out['grad_allreduce_algorithm']}")


if __name__ == "__main__":
    main()
