"""Serving driver: continuous batching with interference-aware chunked
prefill.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --tiny \\
      --requests 8 --mode interference_aware --device tpu_v5e

The engine prices its chunk decisions for the attached TPU's model
(``repro.core.device_model``); ``--device`` names the model instead,
and is required where no TPU is attached.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_config, tiny_config
from repro.core import DEVICES, device_model
from repro.launch.cache import enable_compile_cache
from repro.serve import Engine, EngineConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--mode", default="interference_aware",
                    choices=["serial", "fixed_chunk", "interference_aware"])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--device", choices=sorted(DEVICES), default=None,
                    help="device model to price for (default: the "
                         "attached TPU's)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    if args.device:
        dev = DEVICES[args.device]
    else:
        d0 = jax.devices()[0]
        if d0.platform != "tpu":
            ap.error(f"no TPU attached (found {d0.platform}); pass --device")
        dev = device_model(d0.device_kind)
    cfg = get_config(args.arch)
    if args.tiny:
        cfg = tiny_config(cfg)
    rng = np.random.default_rng(args.seed)
    eng = Engine(cfg, ecfg=EngineConfig(
        max_slots=args.slots, max_len=args.max_len, mode=args.mode), dev=dev)
    for i in range(args.requests):
        plen = int(rng.integers(8, args.max_len - args.max_new - 1))
        prompt = rng.integers(1, cfg.vocab_size, size=plen).tolist()
        eng.submit(prompt, max_new=args.max_new)
    t0 = time.perf_counter()
    metrics = eng.run_until_done()
    dt = time.perf_counter() - t0
    toks = sum(m["new_tokens"] for m in metrics.values())
    print(f"mode={args.mode}: {len(metrics)} requests, {toks} tokens "
          f"in {dt:.2f}s")
    chunks = [e.detail["chunk"] for e in eng.events
              if e.kind == "prefill_chunk"]
    print(f"prefill chunks: n={len(chunks)} sizes={chunks}")
    return metrics


if __name__ == "__main__":
    main()
