"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b --smoke \
      --steps 100 --global-batch 8 --seq-len 128

Uses the host mesh by default (CPU: 1 device). On a real fleet each host
runs this entrypoint under ``jax.distributed.initialize`` and the mesh spans
all processes; the trainer, checkpointing, and data pipeline are already
host-sharded (see data/synthetic.py, train/checkpoint.py).
"""
from __future__ import annotations

import argparse
import importlib

from repro.configs import ARCH_IDS, get_config
from repro.configs.registry import _MODULES
from repro.launch.mesh import make_host_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.train import TrainConfig, Trainer


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--checkpoint-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--checkpoint-every", type=int, default=50)
    ap.add_argument("--model-parallel", type=int, default=1)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    # arch-specific recipe (e.g. minicpm's WSD schedule)
    mod = importlib.import_module(_MODULES[args.arch])
    schedule = getattr(mod, "LR_SCHEDULE", "cosine")

    tc = TrainConfig(
        lr=args.lr, total_steps=args.steps,
        warmup_steps=max(1, args.steps // 10), schedule=schedule,
        grad_accum=args.grad_accum, compress_grads=args.compress_grads,
        checkpoint_dir=args.checkpoint_dir,
        checkpoint_every=args.checkpoint_every)
    mesh = make_host_mesh(model=args.model_parallel)
    trainer = Trainer(cfg, tc, mesh, args.global_batch, args.seq_len)
    history = trainer.run(args.steps)
    if history:
        print(f"final loss: {history[-1]['loss']:.4f} "
              f"(from {history[0]['loss']:.4f})")


if __name__ == "__main__":
    main()
