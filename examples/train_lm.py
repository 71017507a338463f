"""End-to-end LM training driver: ~100M-param model, few hundred steps.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]

Exercises the full distributed trainer stack on the host mesh: sharded
train step (FSDP x TP rules degrade gracefully to 1 device), WSD schedule,
gradient accumulation, async checkpointing + auto-resume, straggler
monitor, and the stateless-seekable data pipeline.
"""
import argparse
import shutil

import jax

from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.compile_cache import enable_compile_cache
from repro.train import TrainConfig, Trainer


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--resume", action="store_true",
                    help="keep checkpoint dir (demonstrates auto-resume)")
    args = ap.parse_args()

    # ~100M params: qwen3-style block at width 512
    cfg = get_config("qwen3-8b").with_(
        name="qwen3-100m", n_layers=8, d_model=512, n_heads=8, n_kv_heads=4,
        head_dim=64, d_ff=1536, vocab_size=32000, remat="none",
        seq_parallel=False, param_dtype="float32", compute_dtype="float32")
    n_params = cfg.param_count()
    print(f"model: {cfg.name}, {n_params / 1e6:.0f}M params")

    ckpt_dir = "/tmp/repro_train_lm"
    if not args.resume:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
    tc = TrainConfig(lr=6e-4, warmup_steps=30, total_steps=args.steps,
                     schedule="wsd", grad_accum=2,
                     checkpoint_dir=ckpt_dir, checkpoint_every=100)
    mesh = make_host_mesh()
    trainer = Trainer(cfg, tc, mesh, global_batch=8, seq_len=256)
    history = trainer.run(args.steps, log_every=25)
    first, last = history[0]["loss"], history[-1]["loss"]
    print(f"loss: {first:.3f} -> {last:.3f} over {len(history)} steps "
          f"({'improved' if last < first else 'NO IMPROVEMENT'})")


if __name__ == "__main__":
    main()
