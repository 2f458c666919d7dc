"""LM training driver over the architecture zoo.

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --reduced \\
      --steps 50 --batch 8 --seq 128
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs.base import TrainConfig
from repro.configs.registry import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.runtime import use_compile_cache
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="train the smoke-scale variant (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--data-parallel", type=int, default=1)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--checkpoint", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    use_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = None
    if args.data_parallel * args.model_parallel > 1:
        mesh = make_host_mesh(args.data_parallel, args.model_parallel)

    tc = TrainConfig(learning_rate=args.lr, optimizer=args.optimizer)
    trainer = Trainer(cfg, tc, args.batch, args.seq, mesh=mesh,
                      seed=args.seed)
    n_params = sum(x.size for x in jax.tree.leaves(trainer.params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"batch={args.batch} seq={args.seq}")
    t0 = time.time()
    final = trainer.run(args.steps, log_every=max(1, args.steps // 20))
    dt = time.time() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({dt/args.steps*1e3:.0f} ms/step); "
          f"loss {trainer.losses[0]:.4f} -> {final:.4f}")
    if args.checkpoint:
        from repro.checkpoint import checkpointer
        checkpointer.save(args.checkpoint, trainer.params,
                          {"arch": cfg.name, "steps": trainer.step_count})
        print(f"checkpoint written to {args.checkpoint}")


if __name__ == "__main__":
    main()
