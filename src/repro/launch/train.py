"""Training driver.

By default it trains the named config at its published width, with the
XLA path, bfloat16 compute and rematerialisation (the Pallas kernels
have no backward pass).  ``--reduced`` trains a tiny same-family config
in float32, which runs on the CPU.  Full-width gemma2-2b with float32
AdamW state needs about 42 GB, more than one TPU v5e holds.

  PYTHONPATH=src python -m repro.launch.train --arch gemma2-2b --reduced \
      --steps 50 --seq-len 128 --batch 8 --ckpt ckpt/
"""

from __future__ import annotations

import argparse
import json
import time

from repro.configs import get
from repro.configs.base import RunConfig, reduced as reduce_cfg
from repro.train import Trainer, TrainerConfig
from repro.dist.fault import FaultConfig
from repro.launch.compile_cache import enable_compile_cache


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma2-2b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config in float32 (CPU)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fault-policy", default="replay",
                    choices=["replay", "continue", "abort"])
    args = ap.parse_args()
    enable_compile_cache()

    if args.reduced:
        cfg = reduce_cfg(get(args.arch))
        rcfg = RunConfig(kernels="xla", dtype="float32", remat=False,
                         learning_rate=args.lr)
    else:
        cfg = get(args.arch)
        rcfg = RunConfig(kernels="xla", learning_rate=args.lr)
    tcfg = TrainerConfig(
        total_steps=args.steps,
        checkpoint_every=args.ckpt_every,
        checkpoint_dir=args.ckpt,
        seed=args.seed,
        fault=FaultConfig(policy=args.fault_policy),
    )
    trainer = Trainer(cfg, rcfg, tcfg, seq_len=args.seq_len,
                      global_batch=args.batch)
    t0 = time.time()
    state = trainer.run()
    dt = time.time() - t0
    losses = [h["loss"] for h in trainer.history if "loss" in h]
    print(json.dumps({
        "arch": cfg.name,
        "steps": int(state["step"]),
        "first_loss": losses[0] if losses else None,
        "last_loss": losses[-1] if losses else None,
        "wall_s": round(dt, 2),
        "replays": trainer.stats.replays,
        "skipped": trainer.stats.skipped,
    }, indent=1))


if __name__ == "__main__":
    main()
