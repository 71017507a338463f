#!/usr/bin/env python
"""Fit and persist the wall-clock phase calibration (DESIGN.md §12).

Measures the seeded workload x ``n_unit`` probe grid on THIS host/backend
(``core.calibrate.collect_probes``: each probe compiles one graph and
times the fused pack/setup/kernel/unpack path behind ``block_until_ready``),
least-squares fits the per-phase overhead factors, and publishes the
result to an :class:`~repro.core.artifact_store.ArtifactStore` as the
named calibration record — the fit ``LogicCompiler``/``ProgramCache``
pick up for ``CompileSpec(n_unit="auto", objective="wallclock")``.

Usage::

    PYTHONPATH=src python tools/calibrate.py --store /var/logic-store \\
        --quick --verify

``--verify`` spawns a FRESH python process that loads the record back
through the store and asserts ``calibrate.fit_count() == 0`` — a warm
process must resolve wallclock specs with *zero re-fits*, the same
counter-pinned contract as the artifact store's zero-compile warm start.
A calibration is host- and backend-specific: re-run this tool after
moving stores across machines or changing jax/interpret configuration.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core import calibrate  # noqa: E402
from repro.core.artifact_store import ArtifactStore  # noqa: E402

#: The --verify child: load from the store in a fresh interpreter, prove
#: the load path never re-fits, and resolve a wallclock auto spec with it.
_VERIFY_SNIPPET = """
import sys
import numpy as np
from repro.core import calibrate
from repro.core.artifact_store import ArtifactStore
from repro.core.compiler import LogicCompiler
from repro.core.gate_ir import random_graph
from repro.core.spec import CompileSpec

store_root, name = sys.argv[1], sys.argv[2]
cal = ArtifactStore(store_root).load_calibration(name)
assert cal is not None, "persisted calibration record not found"
assert calibrate.fit_count() == 0, (
    "loading a persisted calibration must not re-fit "
    f"(fit_count={calibrate.fit_count()})")
compiler = LogicCompiler(calibration=cal)
g = random_graph(np.random.default_rng(7), 16, 400, 8, locality=64)
spec, search = compiler.resolve(
    g, CompileSpec(n_unit="auto", objective="wallclock"))
assert spec.resolved and search.objective == "wallclock"
assert search.alt is not None and search.alt.objective == "cycles"
assert calibrate.fit_count() == 0, "resolve must not re-fit either"
print(f"verify: wallclock pick n_unit={spec.n_unit} "
      f"(cycles pick {search.alt.best_n_unit}), zero re-fits")
"""


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--store", required=True, metavar="DIR",
                    help="artifact-store root directory (created if "
                         "missing)")
    ap.add_argument("--name", default="default",
                    help="calibration record name (default: %(default)s)")
    grid = ap.add_mutually_exclusive_group()
    grid.add_argument("--quick", action="store_true", default=True,
                      help="3-workload x 5-unit probe grid (default)")
    grid.add_argument("--full", dest="quick", action="store_false",
                      help="5-workload x 6-unit probe grid")
    ap.add_argument("--reps", type=int, default=5,
                    help="timed repetitions per probe, min taken "
                         "(default: %(default)s)")
    ap.add_argument("--batch", type=int, default=1024,
                    help="input vectors per probe (default: %(default)s)")
    ap.add_argument("--verify", action="store_true",
                    help="fresh-process load smoke: the persisted record "
                         "must serve wallclock resolution with ZERO "
                         "re-fits (fit_count() == 0)")
    args = ap.parse_args(argv)

    store = ArtifactStore(args.store)
    graphs = calibrate.default_probe_graphs(quick=args.quick)
    units = calibrate.default_probe_units(quick=args.quick)
    print(f"probing {len(graphs)} workloads x {len(units)} unit counts "
          f"(reps={args.reps}, batch={args.batch})...")
    t0 = time.perf_counter()
    probes = calibrate.collect_probes(graphs, units,
                                      n_input_vectors=args.batch,
                                      reps=args.reps)
    cal = calibrate.fit_calibration(probes, meta={
        "grid": "quick" if args.quick else "full",
        "reps": args.reps, "batch": args.batch,
        "n_probes": len(probes)})
    for phase in calibrate.PHASES:
        f = cal.fits[phase]
        coefs = ", ".join(f"{c:.3e}" for c in f.coefs)
        print(f"  {phase:7s} coefs=[{coefs}] offset={f.offset * 1e6:8.1f}us"
              f"  median |err| {f.median_abs_rel_err * 100:5.1f}%")
    path = store.save_calibration(cal, name=args.name)
    print(f"fitted {len(probes)} probes in {time.perf_counter() - t0:.1f}s; "
          f"worst-phase median error "
          f"{cal.median_abs_rel_err() * 100:.1f}%; saved -> {path}")

    if args.verify:
        env = dict(os.environ)
        src = os.path.join(os.path.dirname(__file__), "..", "src")
        env["PYTHONPATH"] = os.pathsep.join(
            p for p in (src, env.get("PYTHONPATH")) if p)
        # this process holds the accelerator; the child only loads and
        # resolves, so it stays on the CPU
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-c", _VERIFY_SNIPPET, args.store, args.name],
            env=env, capture_output=True, text=True)
        sys.stdout.write(proc.stdout)
        sys.stderr.write(proc.stderr)
        if proc.returncode != 0:
            print("verify FAILED", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
