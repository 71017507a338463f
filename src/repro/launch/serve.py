"""Serving launcher: batched prefill + continuous-batching decode.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-8b --smoke \
      --requests 8 --prompt-len 16 --max-new 8
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.transformer import init_params
from repro.serve import Request, RequestBatcher, decode_step, prefill


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch-size", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--context", type=int, default=64)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=args.smoke)
    if cfg.is_encoder:
        raise SystemExit(f"{cfg.name} is encoder-only: no decode serving")
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    batcher = RequestBatcher(args.batch_size)
    for uid in range(args.requests):
        batcher.submit(Request(
            uid=uid,
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len,
                                dtype=np.int32),
            max_new_tokens=args.max_new))

    decode = jax.jit(lambda p, t, c: decode_step(p, cfg, t, c))
    t0 = time.monotonic()
    n_steps = 0
    # slot-parallel serving: prefill each admitted request, merge caches by
    # batch slot, decode all active slots in lockstep (continuous batching)
    caches = [None] * args.batch_size
    while not batcher.idle:
        for slot, req in batcher.admit():
            _, cache = prefill(params, cfg,
                               {"tokens": jnp.asarray(req.prompt)[None]},
                               context=args.context)
            caches[slot] = cache
        active = [i for i, c in enumerate(caches) if c is not None
                  and batcher.slots[i] is not None]
        if not active:
            continue
        toks = np.zeros((args.batch_size,), np.int32)
        for i in active:
            gen = batcher.slots[i].generated
            toks[i] = gen[-1] if gen else batcher.slots[i].prompt[-1]
        nxt = np.full((args.batch_size,), -1, np.int64)
        for i in active:   # per-slot decode (slot caches differ in length)
            logits, caches[i] = decode(params, jnp.asarray([[toks[i]]]),
                                       caches[i])
            nxt[i] = int(jnp.argmax(logits[0, -1]))
            n_steps += 1
        done_before = len(batcher.finished)
        batcher.record_tokens(nxt)
        for i in range(args.batch_size):
            if batcher.slots[i] is None and caches[i] is not None \
                    and len(batcher.finished) > done_before:
                caches[i] = None
    dt = time.monotonic() - t0
    print(f"served {args.requests} requests, {n_steps} decode steps "
          f"in {dt:.2f}s ({n_steps / max(dt, 1e-9):.1f} tok/s)")
    for req in batcher.finished[:4]:
        print(f"  req {req.uid}: {req.generated}")


if __name__ == "__main__":
    main()
