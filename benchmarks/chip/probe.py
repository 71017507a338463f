"""Readings for setting the benchmark's limits, on the chip.

Runs one cell several times in one process (set-up is paid once per run,
but the netlist, program and executables come from the checkout's caches),
one line of JSON per run on standard output:

  python3 benchmarks/chip/probe.py --workload <cell> --seeds 1,2,3 \\
      --seconds 5 [--control]

--control   also reads the control (the binarized source network in the
            netlist's place) on each run's sample: its ``bit_mismatches``
            must come out far above the program's
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from benchmarks.chip.run import configure, configure_jax
    configure()
    import jax

    from benchmarks.chip import cells, harness

    configure_jax(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("probe.py: no TPU found; refusing to run", file=sys.stderr)
        return 1
    cell = cells.resolve(args.workload, ROOT)
    peaks = cells.peaks(ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        r = harness.run_cell(cell, seed, args.seconds, False, root=ROOT,
                             devices=devices, t_start=t0, peaks=peaks,
                             with_control=args.control)
        print(json.dumps({"seed": seed, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
