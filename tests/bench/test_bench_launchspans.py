"""The readers of the engine's runner call (``launchspans.py``) and of
the kernel's time per launch, on synthetic spans and reductions with
known answers, and on a trace the profiler records here."""
import asyncio

import numpy as np
import pytest

from bench_tiny import ROOT

from benchmarks.chip import launchspans as ls
from benchmarks.chip import programspans as ps
from benchmarks.chip.netlist import load_module
from benchmarks.chip.tracefile import CLOSE, OPEN, DeviceReduction

MS = 1e6
SPAN_READERS = ("engine_launch_ms.bulk", "launch_gb_per_s.bulk")
SLAB = 4096 * 2304              # bytes of a conv8 wave's bool slab


def reader(name):
    return load_module(ROOT / "benchmarks" / "chip" / "metrics"
                       / f"{name}.py").read


def wave(start, launch_at, launch_ms, stats):
    """A 5 ms ``logic.engine.step`` span and its ``logic.engine.launch``,
    in ms."""
    return [(ps.STEP, start * MS, 5 * MS, {"rows": 4096}),
            (ls.LAUNCH, launch_at * MS, launch_ms * MS, stats)]


def synthetic(with_stats=True):
    """Three waves in the window [0, 100] ms, one before it, and one
    whose launch the close cuts in half."""
    st = {"in_bytes": SLAB, "vmem_bytes": 37036032} if with_stats else {}
    return ([(OPEN, 0.0, 0.0, {}), (CLOSE, 100 * MS, 0.0, {})]
            + wave(-10, -9, 2, st) + wave(10, 11, 2, st)
            + wave(30, 31, 1, st) + wave(60, 61, 3, st)
            + wave(97, 99, 2, st))


def traced_run(monkeypatch, events, window_s=None):
    monkeypatch.setattr(ps, "xplane_of", lambda run: "synthetic.xplane.pb")
    monkeypatch.setattr(ls, "events_of", lambda path: events)
    return {"cell": "vgg16-conv8.bulk", "trace": [object()],
            "window_s": window_s}


def read_spans(run):
    return {name: reader(name)(run) for name in SPAN_READERS}


def test_launch_readers_to_the_close_marker(monkeypatch):
    # launches of 2, 1 and 3 ms whole, the last clipped to 1 ms; four
    # waves in the window
    got = read_spans(traced_run(monkeypatch, synthetic()))
    assert got == {
        "engine_launch_ms.bulk": pytest.approx((2 + 1 + 3 + 1) / 4),
        "launch_gb_per_s.bulk": pytest.approx(4 * SLAB / (7 * MS))}


def test_launch_readers_clip_to_the_window_seconds(monkeypatch):
    got = read_spans(traced_run(monkeypatch, synthetic(), window_s=0.05))
    assert got == {"engine_launch_ms.bulk": pytest.approx(1.5),
                   "launch_gb_per_s.bulk": pytest.approx(2 * SLAB / (3 * MS))}


def test_a_program_without_the_stats_reads_none_for_bytes(monkeypatch):
    """A program older than ``in_bytes`` still times its launches."""
    got = read_spans(traced_run(monkeypatch, synthetic(with_stats=False)))
    assert got["launch_gb_per_s.bulk"] is None
    assert got["engine_launch_ms.bulk"] == pytest.approx(7 / 4)
    markers = [ev for ev in synthetic() if ev[0] in (OPEN, CLOSE)]
    assert read_spans(traced_run(monkeypatch, markers)) == \
        dict.fromkeys(SPAN_READERS)


def test_readers_are_silent_untraced():
    run = {"cell": "vgg16-conv8.bulk", "trace": None, "window_s": 51.0}
    assert read_spans(run) == dict.fromkeys(SPAN_READERS)
    assert reader("fabric_ms_per_wave.bulk")(run) is None


def test_fabric_ms_per_wave_averages_the_devices():
    def red(kernel_ms, count):
        return DeviceReduction("/device:TPU:0", 51e9,
                               kernel_ns=kernel_ms * MS, kernel_count=count)

    read = reader("fabric_ms_per_wave.bulk")
    assert read({"trace": [red(3000.0, 2000)]}) == pytest.approx(1.5)
    assert read({"trace": [red(3000.0, 2000), red(1000.0, 1000),
                           red(0.0, 0)]}) == pytest.approx(1.25)
    assert read({"trace": [red(0.0, 0)]}) is None
    assert read({"trace": []}) is None


def test_launch_readers_on_a_recorded_trace(tmp_path, monkeypatch):
    """A small front door run traced on this host: each wave's launch
    carries its slab's bytes, which the reader sums."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core.gate_ir import random_graph
    from repro.core.spec import CompileSpec
    from repro.serve import FrontDoor

    from benchmarks.chip import tracefile
    from benchmarks.chip.harness import _opts

    rng = np.random.default_rng(12)
    graph = random_graph(rng, 8, 80, 4, locality=30)
    payloads = [rng.integers(0, 2, (n, 8)).astype(bool)
                for n in (7, 50, 20, 64, 3)]

    async def go():
        door = FrontDoor(spec=CompileSpec(n_unit=8), capacity=64,
                         default_deadline_s=60.0)
        door.register("t", graph)
        async with door:
            await door.submit("t", payloads[0])         # compile outside
            jax.profiler.start_trace(str(tmp_path), profiler_options=_opts())
            with TraceAnnotation(OPEN):
                pass
            for bits in payloads:
                await door.submit("t", bits)
            with TraceAnnotation(CLOSE):
                pass
            jax.profiler.stop_trace()

    asyncio.run(asyncio.wait_for(go(), timeout=90))
    path = tracefile.find_xplane(str(tmp_path))
    monkeypatch.setattr(ps, "xplane_of", lambda run: path)
    spans = ls.of_run({"cell": "cpu", "trace": [object()], "window_s": None})
    launches = spans[ls.LAUNCH]
    assert len(launches) == len(spans[ps.STEP]) == len(payloads)
    assert {st["in_bytes"] for *_, st in launches} == {64 * 8}
    assert all(st["vmem_bytes"] > 0 for *_, st in launches)
    got = read_spans({"cell": "cpu", "trace": [object()], "window_s": None})
    assert got["launch_gb_per_s.bulk"] == pytest.approx(
        len(payloads) * 64 * 8 / sum(b - a for a, b, _ in launches))
    assert got["engine_launch_ms.bulk"] > 0
