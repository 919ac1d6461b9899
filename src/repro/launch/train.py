"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \\
      --steps 200 --batch 8 --seq 256 --tiny --ckpt /tmp/ck

On real hardware: builds the production mesh, applies the fsdp_tp recipe
and runs the same Trainer; on this CPU container use --tiny for the
reduced config (examples/train_tiny_lm.py drives a ~100M model).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs.base import RunConfig
from repro.configs.registry import get_config, tiny_config
from repro.data import DataConfig, Prefetcher, SyntheticLM
from repro.launch.cache import enable_compile_cache
from repro.models import LOCAL_CTX, ParallelContext, build_model
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=None)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--tiny", action="store_true",
                    help="reduced same-family config (CPU-runnable)")
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--n-layers", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.tiny:
        cfg = tiny_config(cfg)
    over = {"attn_impl": "flashref"}
    if args.d_model:
        over["d_model"] = args.d_model
    if args.n_layers:
        over["n_layers"] = args.n_layers
    cfg = cfg.with_overrides(**over)

    model = build_model(cfg)
    run = RunConfig(num_microbatches=args.microbatches,
                    optimizer=args.optimizer)
    tcfg = TrainerConfig(total_steps=args.steps, optimizer=args.optimizer,
                         lr=args.lr, checkpoint_dir=args.ckpt,
                         checkpoint_every=args.ckpt_every)
    trainer = Trainer(model, run, tcfg, ctx=LOCAL_CTX)

    data = Prefetcher(SyntheticLM(cfg, DataConfig(
        seq_len=args.seq, global_batch=args.batch,
        vocab_size=cfg.vocab_size, seed=args.seed)))
    params, _, history = trainer.fit(data, jax.random.PRNGKey(args.seed))
    data.close()
    losses = [h[1] for h in history]
    print(f"\nfinal loss {losses[-1]:.4f} (start {losses[0]:.4f}); "
          f"params {sum(np.prod(p.shape) for p in jax.tree.leaves(params)):,}")
    return losses


if __name__ == "__main__":
    main()
