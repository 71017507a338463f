"""Trace reduction on a small synthetic trace with known answers, and on
one the profiler records here (host spans and the window markers)."""
import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on sys.path)

from benchmarks.chip import tracefile
from benchmarks.chip.tracefile import CLOSE, NO_SPAN, OPEN

MS = 1e6


def synthetic():
    host = [("python3", [(OPEN, 0.0, 0.0), (CLOSE, 100 * MS, 0.0),
                         ("bench.engine.step", 10 * MS, 30 * MS),
                         ("bench.engine.runner", 12 * MS, 2 * MS),
                         ("bench.loadgen.send", 60 * MS, 5 * MS),
                         ("not.ours", 70 * MS, 20 * MS)])]
    dev0 = [("XLA Ops", [("logic_fabric", 14 * MS, 10 * MS),
                         ("fusion.1", 20 * MS, 10 * MS),    # overlaps
                         ("copy", 45 * MS, 5 * MS),
                         ("logic_fabric", 95 * MS, 10 * MS)]),  # past close
            ("XLA Modules", [("jit_run", 14 * MS, 90 * MS)])]
    dev1 = [("XLA Ops", [("logic_fabric", 30 * MS, 40 * MS)])]
    return [("/host:CPU", host), ("/device:TPU:0", dev0),
            ("/device:TPU:1", dev1), ("/device:TPU:0 SparseCore", dev1)]


def test_reduction_by_hand():
    r0, r1 = tracefile.reduce_planes(synthetic())
    assert r0.device == "/device:TPU:0" and r0.window_ns == 100 * MS
    # busy: [14, 30] + [45, 50] + [95, 100] (clipped at the close)
    assert r0.busy_ns == pytest.approx(26 * MS)
    assert r0.kernel_count == 2
    assert r0.kernel_ns == pytest.approx(15 * MS)
    assert r0.ops_ns == pytest.approx({"logic_fabric": 15 * MS,
                                       "fusion.1": 10 * MS, "copy": 5 * MS})
    # idle [0, 14]: nothing open, then the step, then the runner inside it;
    # [30, 45]: the step until 40, then nothing; [50, 95]: the send, and a
    # span that is not the benchmark's counts as nothing
    assert r0.gaps == [
        (pytest.approx(10 * MS), NO_SPAN),
        (pytest.approx(2 * MS), "bench.engine.step"),
        (pytest.approx(2 * MS), "bench.engine.runner"),
        (pytest.approx(10 * MS), "bench.engine.step"),
        (pytest.approx(5 * MS), NO_SPAN),
        (pytest.approx(10 * MS), NO_SPAN),
        (pytest.approx(5 * MS), "bench.loadgen.send"),
        (pytest.approx(30 * MS), NO_SPAN)]
    assert sum(g for g, _ in r0.gaps) == pytest.approx(74 * MS)
    assert r1.busy_ns == pytest.approx(40 * MS)
    assert r1.kernel_ns == pytest.approx(40 * MS)


def test_innermost_span_labels_the_gap():
    planes = synthetic()
    planes[1] = ("/device:TPU:0", [("XLA Ops", [
        ("logic_fabric", 0.0, 12.5 * MS), ("copy", 13.5 * MS, 86.5 * MS)])])
    (r0, _) = tracefile.reduce_planes(planes)
    assert r0.gaps == [(pytest.approx(1 * MS), "bench.engine.runner")]


def test_jax_event_inside_the_span_refines_the_label():
    planes = synthetic()
    planes[0][1][0][1].append(("np.asarray(jax.Array)", 32 * MS, 6 * MS))
    (r0, _) = tracefile.reduce_planes(planes)
    assert r0.gaps[3:7] == [
        (pytest.approx(2 * MS), "bench.engine.step"),
        (pytest.approx(6 * MS), "bench.engine.step > np.asarray(jax.Array)"),
        (pytest.approx(2 * MS), "bench.engine.step"),
        (pytest.approx(5 * MS), NO_SPAN)]


def test_breakdown_averages_devices():
    reds = tracefile.reduce_planes(synthetic())
    b = tracefile.breakdown(reds, top=2)
    assert len(b["device_ops"]) == 2 and len(b["idle_gaps"]) == 2
    assert b["device_ops"][0] == ["logic_fabric", pytest.approx(0.0275)]
    name, secs = b["idle_gaps"][0]
    # no benchmark span open: 55 ms idle on device 0 in 4 pieces, 40 ms
    # on device 1 ([0, 10] and [70, 100]) in 2
    assert name == f"{NO_SPAN} (3 pieces, longest 30 ms)"
    assert secs == pytest.approx(0.0475)
    assert b["idle_gaps"][1] == [
        "bench.engine.step (2 pieces, longest 16 ms)", pytest.approx(0.015)]


def test_window_from_the_open_marker_for_its_seconds():
    (r0, _) = tracefile.reduce_planes(synthetic(), window_s=0.05)
    assert r0.window_ns == 50 * MS
    assert r0.busy_ns == pytest.approx(21 * MS)     # [14, 30] + [45, 50]
    assert r0.kernel_count == 1


def test_missing_markers_are_an_error():
    planes = [("/host:CPU", [("t", [("bench.engine.step", 0.0, 1.0)])]),
              ("/device:TPU:0", [("XLA Ops", [("x", 0.0, 1.0)])])]
    with pytest.raises(ValueError):
        tracefile.reduce_planes(planes)


def test_recorded_trace_has_the_benchmark_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    from benchmarks.chip.harness import _opts
    from benchmarks.chip.spans import Spans

    spans = Spans(True)
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path), profiler_options=_opts())
    with spans(OPEN):
        pass
    with spans("bench.engine.step"):
        f(x).block_until_ready()
    with spans(CLOSE):
        pass
    jax.profiler.stop_trace()
    planes = tracefile.planes_of(tracefile.find_xplane(str(tmp_path)))
    spans_found = tracefile.host_spans(planes)
    names = [n for _, _, n in spans_found if n.startswith("bench.")]
    assert names == [OPEN, "bench.engine.step", CLOSE]
    lo, hi = tracefile.window_of(spans_found)
    assert hi > lo


def test_recorded_tpu_trace_slice():
    """20 ms of a traced ``lenet5-head.bulk`` window on a TPU v5 lite
    (window markers set at the slice's ends)."""
    import json
    from pathlib import Path

    raw = json.loads((Path(__file__).with_name("tpu_trace_slice.json"))
                     .read_text())
    planes = [(p, [(ln, [tuple(e) for e in ev]) for ln, ev in lines])
              for p, lines in raw["planes"]]
    (r,) = tracefile.reduce_planes(planes)
    ops = dict(planes)["/device:TPU:0"][0][1]
    fabric = [(s, d) for n, s, d in ops if "logic_fabric" in n]
    assert r.kernel_count == len(fabric) == 8
    assert r.window_ns == 20e6
    assert r.kernel_ns <= r.busy_ns <= r.window_ns
    assert sum(g for g, _ in r.gaps) + r.busy_ns == pytest.approx(20e6)
    labels = {lab for _, lab in r.gaps}
    assert "bench.engine.step > np.asarray(jax.Array)" in labels
    assert all(lab.startswith("bench.") or lab == NO_SPAN for lab in labels)
    b = tracefile.breakdown([r])
    assert b["device_ops"][0][0] == "%logic_fabric.1"
