"""Training launcher.

Local run (CPU/debug, reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch granite_3_2b --smoke \
        --steps 50 --batch 8 --seq 64

Production pod run (on real hardware this process runs per-host under the
TPU runtime; the mesh/'sharding code is identical to the dry-run — which is
how we prove it without hardware):
    python -m repro.launch.train --arch nemotron_4_340b --steps 1000
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs.base import ARCH_IDS, canon, get_config
from repro.configs.smoke import reduce
from repro.data.synthetic import DataConfig, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import TrainConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, help="|".join(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/leapjax_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(canon(args.arch))
    if args.smoke:
        cfg = reduce(cfg)
    data = SyntheticLM(
        DataConfig(
            cfg.vocab_size,
            args.seq,
            args.batch,
            embed_dim=None if cfg.embed_inputs else cfg.d_model,
        )
    )
    tcfg = TrainConfig(
        n_micro=args.n_micro,
        accum_dtype=cfg.grad_accum_dtype,
        optimizer=OptimizerConfig(
            peak_lr=args.lr,
            warmup_steps=max(args.steps // 10, 1),
            total_steps=args.steps,
            state_dtype=cfg.opt_state_dtype,
        ),
    )
    tr = Trainer(
        cfg,
        tcfg,
        TrainerConfig(
            total_steps=args.steps,
            ckpt_every=args.ckpt_every,
            ckpt_dir=args.ckpt_dir,
            log_every=max(args.steps // 20, 1),
        ),
        data,
    )
    resumed = tr.restore_or_init()
    if resumed:
        print(f"resumed from step {resumed}")
    tr.run(on_step=lambda s, m: print(
        f"step {s:6d}  loss {m['loss']:.4f}  gnorm {m['grad_norm']:.3f}  lr {m['lr']:.2e}"
    ))


if __name__ == "__main__":
    main()
