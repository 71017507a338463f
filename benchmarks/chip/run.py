"""On-chip benchmark of the served logic path: one run of one cell.

  python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
      --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json`` (see ``cells.py``). The run refuses to start
(exit 1, no result) unless JAX's first device is a TPU and there are as
many as the cell asks for. Progress and the set-up split go to standard
error, ending with each number compared beside its limit; the last line of
standard output is the result as one JSON object. With ``--trace 0`` its
metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.

JAX's persistent compile cache, the artifact store and the traces live
under ``benchmarks/chip/.cache/`` of the checkout, so only the first run of
a cell in a checkout compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
CACHE = ROOT / "benchmarks" / "chip" / ".cache"
#: the TPU runtime's premapped host buffer. On v5e hosts without transparent
#: hugepages the runtime's default buffer took 4.7-12.3 s to map at start,
#: varying run to run; 256 MiB maps in under 2 s and still holds a wave's
#: transfers many times over (1.6 MB in, 0.3 MB out per 4096 samples)
PREMAPPED_BYTES = 256 << 20


def configure() -> None:
    """Keep JAX's persistent compile cache and the TPU runtime's logs in
    the checkout and size the runtime's premapped buffer: before JAX is
    imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE / "jax")
    os.environ["TPU_LOG_DIR"] = str(CACHE / "tpu_logs")
    os.environ.setdefault("TPU_PREMAPPED_BUFFER_SIZE", str(PREMAPPED_BYTES))


def configure_jax(jax) -> None:
    """Cache every compiled program, however small or quick."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    configure()
    try:
        import repro  # noqa: F401
    except ImportError:
        print("run.py: the program under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    from benchmarks.chip import cells, harness

    cell = cells.resolve(args.workload, ROOT)
    peaks = cells.peaks(ROOT)
    import jax

    configure_jax(jax)
    t_import = time.perf_counter() - T_START
    devices = jax.devices()
    t_devices = time.perf_counter() - T_START - t_import
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU found (JAX's devices are "
              f"{devices[0].platform}); refusing to run", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"run.py: {cell.name} needs {cell.chips} TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    if devices[0].device_kind not in peaks:
        print(f"run.py: device kind {devices[0].device_kind!r} is not in "
              f"peaks.json", file=sys.stderr)
        return 1
    harness.log(f"{cell.name} seed {args.seed} seconds {args.seconds} trace "
                f"{args.trace} on {len(devices)} x {devices[0].device_kind}, "
                f"jax {jax.__version__}; start: imports {t_import:.3f} s, "
                f"devices {t_devices:.3f} s")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), root=ROOT, devices=devices,
                              t_start=T_START, peaks=peaks)
    for name, c in result["checks"].items():
        bound = f"limit {c['limit']}" if "limit" in c else f"min {c['min']}"
        print(f"check {name} {c['value']} {bound}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    # leave without the TPU runtime's teardown once the output is out: a
    # traced run once hung there for minutes after printing its result
    rc = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(rc)
