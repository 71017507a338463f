"""Cells, configurations, traffic mixes and metrics, found by name.

``BENCHMARK.json`` at the checkout's root names them; each lives in a file
of its own under ``benchmarks/chip/``:

  configs/<config>.json     the configuration as it is run (the ``file``
                            of its ``configs`` entry)
  configs/<config>.npz      its netlist, named by ``netlist_file``
  traffic/<traffic>.json    the load generator's parameters
  metrics/<metric>.py       ``read(run) -> float | None`` for one metric

A later cell or metric is new files plus new entries, with no edit here.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path

from benchmarks.chip.netlist import load_module

#: the checkout's root: this file is ``<root>/benchmarks/chip/cells.py``
ROOT = Path(__file__).resolve().parents[2]


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: object                    # callable(run) -> float | None


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: tuple
    per_layer: tuple

    def metrics(self, trace: bool) -> tuple:
        return self.per_layer if trace else self.end_to_end


def bench_dir(root: Path) -> Path:
    return root / "benchmarks" / "chip"


def _metric(root: Path, m: dict) -> Metric:
    mod = load_module(bench_dir(root) / "metrics" / f"{m['name']}.py")
    return Metric(m["name"], m["unit"], mod.read)


def resolve(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its files."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(there are {sorted(work)})")
    w = work[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads(
        (bench_dir(root) / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic,
                end_to_end=tuple(_metric(root, m) for m in e2e),
                per_layer=tuple(_metric(root, m) for m in layer))


def peaks(root: Path = ROOT) -> dict:
    return json.loads((bench_dir(root) / "peaks.json").read_text())
