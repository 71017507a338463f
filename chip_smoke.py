"""Chip smoke test: the served logic path, compiled, on a TPU.

Serves the LeNet-5 classifier head (400 -> 120 -> 84 hidden bits, 10
classes) as compiled combinational logic through the normal entry points:

  FlowConfig / build_classifier   train a binarized MLP on the seeded
                                  synthetic task and NullaNet-convert its
                                  hidden stack (ISF mode)
  FrontDoor -> LogicEngine        one tenant serving the composed hidden
                                  stack, capacity 4096 samples (one
                                  128-lane word block)
  -> fabric kernel                compiled by Mosaic, not interpreted

and checks every served result bit-for-bit against ``LogicGraph.evaluate``
on the host.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # only the sharded engine, on 4 chips

It refuses to run (non-zero exit, no result line) unless JAX's first
device is a TPU. Any failed phase raises and exits non-zero. The last line
of standard output is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

FEATURES, HIDDEN, CLASSES = 400, (120, 84), 10     # LeNet-5 fc1..fc3 widths
CAPACITY = 4096                  # samples per wave: one 128-lane word block
N_UNIT = 32
TRAIN_STEPS = 200
N_SAMPLES = 4000                 # 3/4 train and calibrate the ISF conversion
#: ragged request sizes (samples), all drawn from the validation split
REQUEST_SIZES = (1, 1000, 37, 256, 511, 3, 777, 129)
WARMUP_SIZE = 64
DEADLINE_S = 3600.0              # loose: nothing may shed on time
TENANT = "lenet5-head"


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def build_model(seed: int):
    """Train the binarized MLP and convert its hidden stack to logic."""
    import numpy as np

    from repro.core.nullanet import BinaryMLPConfig, train_binary_mlp
    from repro.core.spec import CompileSpec
    from repro.flow import FlowConfig, build_classifier

    cfg = FlowConfig(n_features=FEATURES, hidden=HIDDEN, n_classes=CLASSES,
                     n_samples=N_SAMPLES, train_steps=TRAIN_STEPS,
                     spec=CompileSpec(n_unit=N_UNIT), mode="isf", seed=seed)
    xt, yt, xv, yv = cfg.load_data()
    t0 = time.perf_counter()
    params = train_binary_mlp(
        BinaryMLPConfig(FEATURES, HIDDEN, CLASSES, seed=seed), xt, yt,
        steps=cfg.train_steps)
    params = {k: np.asarray(v) for k, v in params.items()}
    log(f"train_s={time.perf_counter() - t0:.3f} "
        f"({cfg.train_steps} steps, {len(xt)} samples)")
    t0 = time.perf_counter()
    clf = build_classifier(params, len(HIDDEN) + 1, xt, cfg.spec,
                           mode=cfg.mode)
    log(f"convert_s={time.perf_counter() - t0:.3f} "
        f"(ISF calibration on {len(xt)} samples)")
    for st in clf.layer_stats():
        log(f"  {st['name']}: {st['n_inputs']}->{st['n_outputs']} "
            f"{st['n_gates']} gates, {st['n_steps']} steps, "
            f"n_addr {st['n_addr']}, n_unit {st['n_unit']}")
    return clf, params, xv, yv


async def serve(engine, graph, warmup, batches):
    """Warm-up request, then every batch at once through one FrontDoor."""
    from repro.serve.frontdoor import FrontDoor

    door = FrontDoor(engine, max_queue=64, default_deadline_s=DEADLINE_S)
    door.register(TENANT, graph)
    async with door:
        t0 = time.perf_counter()
        warm = await door.submit(TENANT, warmup)
        warm_s = time.perf_counter() - t0
        door.reset_metrics()
        t0 = time.perf_counter()
        results = await asyncio.gather(
            *(door.submit(TENANT, b) for b in batches))
        serve_s = time.perf_counter() - t0
    return warm, results, door.metrics(), warm_s, serve_s


def run(args, devices, cache_events) -> None:
    import jax
    import numpy as np

    from repro.flow import hard_forward, input_bits
    from repro.serve import LogicEngine

    clf, params, xv, yv = build_model(args.seed)
    graph = clf.stacked_graph

    if args.chips == 1:
        engine = LogicEngine(clf.spec, capacity=CAPACITY, shard=False)
    else:
        from jax.sharding import Mesh
        engine = LogicEngine(clf.spec, capacity=CAPACITY, shard=True,
                             mesh=Mesh(np.asarray(devices), ("data",)))
    t0 = time.perf_counter()
    entry = engine.cache.get(graph, engine.spec)
    log(f"program_compile_s={time.perf_counter() - t0:.3f} "
        f"gates={graph.n_gates} steps={sum(p.n_steps for p in entry.programs)}"
        f" n_addr={max(p.n_addr for p in entry.programs)} "
        f"programs={len(entry.programs)} n_unit={engine.spec.n_unit}")

    bits = input_bits(xv)
    rng = np.random.default_rng(args.seed)
    starts = [int(rng.integers(0, len(xv) - n + 1)) for n in REQUEST_SIZES]
    batches = [bits[s:s + n] for s, n in zip(starts, REQUEST_SIZES)]
    warm, results, m, warm_s, serve_s = asyncio.run(
        serve(engine, graph, bits[:WARMUP_SIZE], batches))
    log(f"warmup_s={warm_s:.3f} (first wave: XLA + Mosaic compile and run)")
    log(f"serve_s={serve_s:.3f} for {len(batches)} requests, "
        f"{sum(REQUEST_SIZES)} samples, "
        f"{m['engine']['invocations']} waves")
    log(f"compile_cache hits={cache_events.hits} "
        f"misses={cache_events.misses}")

    if m["offered"] != len(batches) or m["completed"] != len(batches):
        raise SystemExit(f"offered {m['offered']}, completed "
                         f"{m['completed']} of {len(batches)} requests")
    if m["shed"]:
        raise SystemExit(f"{m['shed']} requests shed: {m['shed_by_code']}")

    mismatches = int((warm != graph.evaluate(bits[:WARMUP_SIZE])).sum())
    for b, got in zip(batches, results):
        mismatches += int((got != graph.evaluate(b)).sum())
    log(f"bit_mismatches={mismatches} (vs LogicGraph.evaluate)")
    if mismatches:
        raise SystemExit(f"{mismatches} served bits differ from the oracle")

    served = np.concatenate(results)
    labels = np.concatenate([yv[s:s + n] for s, n in zip(starts,
                                                         REQUEST_SIZES)])
    logic_acc = float((clf.logits_from_hidden(served).argmax(-1)
                       == labels).mean())
    _, logits = hard_forward(params, np.concatenate(batches), len(HIDDEN) + 1)
    bin_acc = float((logits.argmax(-1) == labels).mean())
    log(f"accuracy on served samples: logic={logic_acc:.4f} "
        f"binarized={bin_acc:.4f}")

    runner = next(iter(entry.runners.values()))
    hlo = runner.lower(jax.ShapeDtypeStruct((engine.capacity, graph.n_inputs),
                                            bool)).compile().as_text()
    if "tpu_custom_call" not in hlo:
        raise SystemExit("the served runner's HLO has no tpu_custom_call: "
                         "the fabric kernel did not compile for the TPU")
    log("served runner HLO contains tpu_custom_call")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: serve through the shard_map engine on a "
                         "4-device mesh (and nothing else)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    try:
        import repro  # noqa: F401
    except ImportError:
        print("chip_smoke: the repro package (src/) is not next to this "
              "script", file=sys.stderr)
        return 2
    import jax

    from repro.launch.compile_cache import CacheEvents, enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's devices are "
              f"{devices[0].platform}); refusing to run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 1
    devices = devices[:args.chips]
    log(f"cache_dir={enable_compile_cache()}")
    cache_events = CacheEvents()
    kind = devices[0].device_kind
    log(f"device_kind={kind} count={len(devices)} jax={jax.__version__}")
    run(args, devices, cache_events)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
