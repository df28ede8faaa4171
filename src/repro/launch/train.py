"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Selects any assigned architecture (full or reduced), builds the host mesh,
and runs the fault-tolerant loop with scda checkpointing.  On a real
multi-host TPU fleet the same entry point runs per host after
``jax.distributed.initialize`` (the checkpoint layer keys windows off each
process's addressable shards automatically).
"""
from __future__ import annotations

import argparse
import logging

import jax

from repro.configs import REGISTRY, get_config, smoke
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.optim.adamw import AdamWConfig
from repro.train.loop import TrainLoopConfig, train


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=sorted(REGISTRY))
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="reduced same-family config (default on CPU)")
    ap.add_argument("--no-smoke", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro-ckpts")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--ckpt-compressed", action="store_true")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--data-par", type=int, default=0,
                    help="data axis size (0 = all local devices)")
    ap.add_argument("--model-par", type=int, default=1)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO, format="%(name)s: %(message)s")
    configure_compile_cache()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke(cfg)
    dp = args.data_par or max(1, jax.device_count() // args.model_par)
    mesh = make_host_mesh(dp, args.model_par)
    loop = TrainLoopConfig(
        total_steps=args.steps, ckpt_every=args.ckpt_every,
        ckpt_dir=f"{args.ckpt_dir}/{cfg.name}", ckpt_keep=3,
        ckpt_compressed=args.ckpt_compressed,
        grad_compress=args.grad_compress)
    out = train(cfg, loop,
                AdamWConfig(lr=args.lr, total_steps=args.steps),
                mesh=mesh, seq_len=args.seq_len,
                global_batch=args.global_batch)
    print(f"done: start_step={out['start_step']} "
          f"final_loss={out['losses'][-1]:.4f} "
          f"checkpoints={out['manager'].all_steps()}")


if __name__ == "__main__":
    main()
