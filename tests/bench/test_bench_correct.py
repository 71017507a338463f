"""``correct`` separates the served path from its control and from the
faults a serving cell can have, and the benchmark refuses to run without
its chip or its program.

Each run below skips the harness's look for a chip and drives the rest of
a run of a dummy cell on this host, at a size a test run can hold."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from bench_tiny import ROOT, make_root, run

from benchmarks.chip.spans import TracedEngine


class FaultyEngine(TracedEngine):
    """The served path with a fault planted in each wave's result."""

    fault = None

    def _build_runner(self, entry):
        inner = super()._build_runner(entry)

        def runner(bits):
            return self.plant(np.array(inner(bits)))

        return runner

    def plant(self, out):
        if self.fault == "state_unchanged":      # every wave repeats the first
            if not hasattr(self, "_first"):
                self._first = out
            return self._first
        if self.fault == "half_batch":           # half the rows not computed
            out[out.shape[0] // 2:] = False
        elif self.fault == "altered_answer":     # one bit flipped where made
            out[0, 0] = ~out[0, 0]
        elif self.fault == "exchange_left_out":  # only the first chip's rows
            out[out.shape[0] // len(self.mesh.devices.flat):] = False
        return out


def faulty(name):
    return type(f"Faulty_{name}", (FaultyEngine,), {"fault": name})


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


def test_program_is_correct_and_its_control_is_not(root):
    r = run(root, "tiny.closed", with_control=True)
    assert r["correct"]
    assert r["checks"]["bit_mismatches"] == {"value": 0, "limit": 0}
    assert r["checks"]["bits_compared"]["value"] > 0
    assert r["control"]["bit_mismatches"]["value"] > 0
    assert r["control"]["bits_compared"] == r["checks"]["bits_compared"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "altered_answer"])
def test_a_fault_on_the_timed_path_is_not_correct(root, fault):
    r = run(root, "tiny.closed", engine_cls=faulty(fault))
    assert not r["correct"]
    assert r["checks"]["bit_mismatches"]["value"] > 0


def test_leaving_out_the_exchange_between_chips_is_not_correct(tmp_path):
    code = textwrap.dedent(f"""
        import json, sys
        sys.path.insert(0, {str(ROOT / 'tests' / 'bench')!r})
        from pathlib import Path
        from bench_tiny import make_root, run
        from test_bench_correct import faulty
        root = make_root(Path({str(tmp_path)!r}))
        good = run(root, "tiny.x4")
        bad = run(root, "tiny.x4", engine_cls=faulty("exchange_left_out"))
        print(json.dumps([good, bad]))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    good, bad = json.loads(p.stdout.strip().splitlines()[-1])
    assert good["device"]["count"] == 4 and good["correct"]
    assert not bad["correct"]
    assert bad["checks"]["bit_mismatches"]["value"] > 0


def test_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "benchmarks/chip/run.py",
                        "--workload", "lenet5-head.bulk", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_refuses_to_run_with_only_the_benchmark(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(bench["command"] + ["--workload", "lenet5-head.bulk",
                                           "--seed", "1", "--seconds", "1",
                                           "--trace", "0"],
                       env=env, cwd=tmp_path, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout == ""
