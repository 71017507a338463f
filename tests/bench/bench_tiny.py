"""Shared helpers of the benchmark's CPU tests: a checkout root holding
the benchmark plus a dummy configuration, traffic mixes and a per-layer
metric, each added as new files and new ``BENCHMARK.json`` entries."""
from __future__ import annotations

import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import netlist  # noqa: E402

METRIC = '''
def read(run):
    return run["gates"] / run["n_outputs"]
'''

LATENCY = '''
import numpy as np


def read(run):
    done = np.where(np.isnan(run["done"]), run["t_close"] + run["drain_s"],
                    run["done"])
    lat = done - run["due"]
    return float(np.percentile(lat, {q})) * 1e3 if lat.size else None
'''

CONFIG = {"name": "tiny", "n_unit": 8, "capacity_per_device": 256,
          "netlist_file": "benchmarks/chip/configs/tiny.npz"}
CLOSED = {"loop": "closed", "clients": 3,
          "size": {"dist": "uniform", "min": 1, "max": 400, "unit": 1},
          "deadline_s": 60.0, "max_queue": 64, "check_share": 0.5,
          "pool_rows": 1024}
OPEN = {"loop": "open", "rate_rps": 200,
        "size": {"dist": "geometric", "mean": 8, "max": 64, "unit": 1},
        "deadline_s": 10.0, "max_queue": 4096, "check_share": 0.5,
        "pool_rows": 1024}


def tiny_netlist(path: Path, n_inputs=12, n_gates=60, n_outputs=5,
                 seed=3) -> str:
    """A seeded random netlist and a one-layer source network, saved as
    the benchmark keeps a configuration's netlist; returns its
    fingerprint."""
    rng = np.random.default_rng(seed)
    gates = [(int(rng.integers(1, 9)), int(rng.integers(2, 2 + n_inputs + i)),
              int(rng.integers(2, 2 + n_inputs + i))) for i in range(n_gates)]
    top = 2 + n_inputs + n_gates
    w = rng.normal(size=(n_inputs, n_outputs)).astype(np.float32)
    netlist.save(path, n_inputs, gates, range(top - n_outputs, top),
                 [(w, np.zeros(n_outputs, np.float32))])
    return netlist.load(path).fingerprint


def make_root(tmp: Path) -> Path:
    """A checkout root: the benchmark as committed, plus the dummy cells
    ``tiny.closed``, ``tiny.open`` and ``tiny.x4``, their latency metrics
    and the per-layer metric ``gates_per_output.tiny``, added as files and
    entries only."""
    chip = tmp / "benchmarks" / "chip"
    shutil.copytree(ROOT / "benchmarks" / "chip", chip,
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (chip / "metrics" / "gates_per_output.tiny.py").write_text(METRIC)
    for name, q in (("latency_p95_ms", 95), ("latency_p50_ms", 50)):
        (chip / "metrics" / f"{name}.py").write_text(LATENCY.format(q=q))
    config = {**CONFIG,
              "fingerprint": tiny_netlist(chip / "configs" / "tiny.npz")}
    (chip / "configs" / "tiny.json").write_text(json.dumps(config))
    (chip / "traffic" / "tiny-closed.json").write_text(json.dumps(CLOSED))
    (chip / "traffic" / "tiny-open.json").write_text(json.dumps(OPEN))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "tiny", "source": "synthetic",
                             "file": "benchmarks/chip/configs/tiny.json",
                             "reduced": [], "why": "CPU test"})
    cells = {"tiny.closed": ("tiny-closed", 1), "tiny.open": ("tiny-open", 1),
             "tiny.x4": ("tiny-closed", 4)}
    for name, (traffic, chips) in cells.items():
        bench["workloads"].append({"name": name, "config": "tiny",
                                   "traffic": traffic, "chips": chips,
                                   "why": "CPU test"})
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    e2e["samples_per_s"]["workloads"] += ["tiny.closed", "tiny.x4"]
    for name in ("latency_p95_ms", "latency_p50_ms"):
        bench["end_to_end"].append({
            "name": name, "unit": "ms", "better": "lower", "bound": 0.05,
            "source": "host_clock", "workloads": ["tiny.open"]})
    bench["per_layer"].append({
        "name": "gates_per_output.tiny", "unit": "gates", "better": "lower",
        "source": "program_counter", "layer": "whole served path",
        "moves": "samples_per_s", "workloads": ["tiny.closed"]})
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


def run(root: Path, cell_name: str, *, seed: int = 2**31 + 12345,
        seconds: float = 0.5, engine_cls=None, with_control=False) -> dict:
    """One run of a dummy cell on this host's devices, without the
    harness's look for a chip."""
    import jax

    from benchmarks.chip import cells, harness

    cell = cells.resolve(cell_name, root)
    kw = {} if engine_cls is None else {"engine_cls": engine_cls}
    return harness.run_cell(cell, seed, seconds, False, root=root,
                            devices=jax.devices(), t_start=time.perf_counter(),
                            with_control=with_control, **kw)
