"""The benchmark's cells, configurations, traffic mixes and metrics are
found by name, and a new one is only new files and entries."""
import json
import re

import numpy as np
import pytest

from bench_tiny import ROOT, make_root, run, tiny_netlist

from benchmarks.chip import cells, netlist

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(workload):
    cell = cells.resolve(workload)
    entry = next(w for w in BENCH["workloads"] if w["name"] == workload)
    assert cell.chips == entry["chips"]
    assert cell.config["name"] == entry["config"]
    assert cell.traffic["loop"] in ("closed", "open")
    e2e = {m.name for m in cell.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    moved = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    assert all(moved[m.name] in e2e for m in cell.per_layer)
    assert all(callable(m.read) for m in cell.end_to_end + cell.per_layer)


def test_benchmark_json_keeps_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert set(c["reduced"]) <= set(cfg)
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)


def test_a_cell_config_traffic_and_metric_are_added_by_files(tmp_path):
    root = make_root(tmp_path)
    cell = cells.resolve("tiny.closed", root)
    assert cell.config["n_unit"] == 8 and cell.traffic["clients"] == 3
    assert [m.name for m in cell.per_layer] == ["gates_per_output.tiny"]
    r = run(root, "tiny.closed")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {"samples_per_s", "setup_s"}
    assert r["metrics"]["samples_per_s"]["value"] > 0
    assert list(r)[-1] == "checks"
    run_rec = {"gates": 60, "n_outputs": 5}
    assert cell.per_layer[0].read(run_rec) == 12.0


def test_unknown_workload_is_refused(tmp_path):
    with pytest.raises(KeyError):
        cells.resolve("no-such.cell")


def test_open_loop_cell_reports_latency(tmp_path):
    root = make_root(tmp_path)
    r = run(root, "tiny.open", seconds=1.0)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert set(m) == {"latency_p95_ms", "latency_p50_ms", "setup_s"}
    assert 0 < m["latency_p50_ms"]["value"] <= m["latency_p95_ms"]["value"]
    assert r["attempted"] == 200      # rate x seconds, the same every seed
    assert np.isfinite(m["setup_s"]["value"])


@pytest.mark.parametrize("config", [c["name"] for c in BENCH["configs"]])
def test_committed_netlist_matches_its_fingerprint(config):
    entry = next(c for c in BENCH["configs"] if c["name"] == config)
    cfg = json.loads((ROOT / entry["file"]).read_text())
    nl, _ = netlist.of_config(ROOT, cfg)
    assert nl.n_gates > 0 and nl.n_inputs == nl.layers[0][0].shape[0]


def test_a_netlist_whose_fingerprint_differs_is_refused(tmp_path):
    root = make_root(tmp_path)
    cfg = json.loads((root / "benchmarks/chip/configs/tiny.json").read_text())
    tiny_netlist(root / cfg["netlist_file"], seed=4)
    with pytest.raises(ValueError, match="fingerprint"):
        netlist.of_config(root, cfg)
