"""The readers of the program's own spans (``programspans.py``) on
synthetic spans with known answers, and on a trace the profiler records
here."""
import asyncio

import numpy as np
import pytest

from bench_tiny import ROOT

from benchmarks.chip import programspans as ps
from benchmarks.chip.netlist import load_module
from benchmarks.chip.tracefile import CLOSE, OPEN

MS = 1e6
ADMIT, LAUNCH = "logic.engine.admit", "logic.engine.launch"   # read by none
READERS = ("engine_slab_ms.bulk", "engine_fetch_ms.bulk",
           "engine_scatter_ms.bulk", "wave_gap_ms.bulk",
           "wave_occupancy_pct.bulk")


def reader(name):
    return load_module(ROOT / "benchmarks" / "chip" / "metrics"
                       / f"{name}.py").read


def wave(start, dur, rows, phases, capacity=4096):
    """A ``logic.engine.step`` span and its phases, ``(name, start, dur)``
    in ms."""
    out = [(ps.STEP, start * MS, dur * MS,
            {"rows": rows, "capacity": capacity, "chunks": 2,
             "finished": 1})]
    out += [(n, s * MS, d * MS, {}) for n, s, d in phases]
    return out


def synthetic():
    """Two executor threads' waves, listed thread by thread: thread A
    serves waves 1, 3 and 4, thread B wave 2; wave 0 ends before the
    window opens and wave 4 runs past the close at 100 ms."""
    thread_a = (
        wave(-8, 6, 4096, [(ps.SLAB, -7, 2)])
        + wave(10, 10, 3000, [(ADMIT, 10, 1), (ps.SLAB, 11, 2),
                              (LAUNCH, 13, 1), (ps.FETCH, 14, 4),
                              (ps.SCATTER, 18, 1.5)])
        + wave(35, 10, 1000, [(ps.SLAB, 36, 3), (ps.FETCH, 39, 5),
                              (ps.SCATTER, 44, 0.5)])
        + wave(95, 10, 4096, [(ps.SLAB, 96, 2), (ps.FETCH, 98, 4),
                              (ps.SCATTER, 102, 1)]))
    thread_b = wave(22, 8, 4096, [(ADMIT, 22, 0.5), (ps.SLAB, 22.5, 1),
                                  (LAUNCH, 23.5, 0.5), (ps.FETCH, 24, 3),
                                  (ps.SCATTER, 27, 2)])
    markers = [(OPEN, 0.0, 0.0, {}), (CLOSE, 100 * MS, 0.0, {})]
    return markers + thread_a + thread_b


def traced_run(monkeypatch, events, window_s=None):
    """A traced run whose profile holds ``events``."""
    monkeypatch.setattr(ps, "xplane_of", lambda run: "synthetic.xplane.pb")
    monkeypatch.setattr(ps, "events_of", lambda path: events)
    return {"cell": "lenet5-head.bulk", "trace": [object()],
            "window_s": window_s}


def read_all(run):
    return {name: reader(name)(run) for name in READERS}


def test_readers_to_the_close_marker(monkeypatch):
    # waves 1-3 whole, wave 4 clipped to [95, 100]: its fetch to 2 ms,
    # its scatter after the close left out; wave 0 left out
    got = read_all(traced_run(monkeypatch, synthetic()))
    assert got == {
        "engine_slab_ms.bulk": pytest.approx((2 + 1 + 3 + 2) / 4),
        "engine_fetch_ms.bulk": pytest.approx((4 + 3 + 5 + 2) / 4),
        "engine_scatter_ms.bulk": pytest.approx((1.5 + 2 + 0.5) / 4),
        # ends 20, 30, 45 to starts 22, 35, 95, across both threads
        "wave_gap_ms.bulk": pytest.approx((2 + 5 + 50) / 3),
        "wave_occupancy_pct.bulk":
            pytest.approx((3000 + 4096 + 1000 + 4096) / (4 * 4096) * 100)}


def test_readers_clip_to_the_window_seconds(monkeypatch):
    got = read_all(traced_run(monkeypatch, synthetic(), window_s=0.06))
    assert got == {
        "engine_slab_ms.bulk": pytest.approx(2.0),
        "engine_fetch_ms.bulk": pytest.approx(4.0),
        "engine_scatter_ms.bulk": pytest.approx(4 / 3),
        "wave_gap_ms.bulk": pytest.approx(3.5),
        "wave_occupancy_pct.bulk":
            pytest.approx((3000 + 4096 + 1000) / (3 * 4096) * 100)}


def test_readers_are_silent_untraced():
    run = {"cell": "lenet5-head.bulk", "trace": None, "window_s": 51.0}
    assert read_all(run) == dict.fromkeys(READERS)


def test_a_missing_span_name_reads_as_none(monkeypatch):
    no_slab = [ev for ev in synthetic() if ev[0] != ps.SLAB]
    got = read_all(traced_run(monkeypatch, no_slab))
    assert got["engine_slab_ms.bulk"] is None
    assert got["engine_fetch_ms.bulk"] == pytest.approx(3.5)
    renamed = [("logic.engine.wave" if n == ps.STEP else n, s, d, st)
               for n, s, d, st in synthetic()]
    assert read_all(traced_run(monkeypatch, renamed)) == \
        dict.fromkeys(READERS)
    # a program without spans of its own: only the window markers
    markers = [ev for ev in synthetic() if ev[0] in (OPEN, CLOSE)]
    assert read_all(traced_run(monkeypatch, markers)) == \
        dict.fromkeys(READERS)


def test_readers_on_a_recorded_trace(tmp_path, monkeypatch):
    """A small front door run traced on this host: every reader finds its
    spans, and the occupancy is the engine's own."""
    import jax
    from jax.profiler import TraceAnnotation

    from repro.core.gate_ir import random_graph
    from repro.core.spec import CompileSpec
    from repro.serve import FrontDoor

    from benchmarks.chip import tracefile
    from benchmarks.chip.harness import _opts

    rng = np.random.default_rng(11)
    graph = random_graph(rng, 8, 80, 4, locality=30)
    payloads = [rng.integers(0, 2, (n, 8)).astype(bool)
                for n in (7, 50, 20, 64, 3)]

    async def go():
        door = FrontDoor(spec=CompileSpec(n_unit=8), capacity=64,
                         default_deadline_s=60.0)
        door.register("t", graph)
        async with door:
            await door.submit("t", payloads[0])         # compile outside
            door.engine.reset_telemetry()
            jax.profiler.start_trace(str(tmp_path), profiler_options=_opts())
            with TraceAnnotation(OPEN):
                pass
            for bits in payloads:
                await door.submit("t", bits)
            with TraceAnnotation(CLOSE):
                pass
            jax.profiler.stop_trace()
            return door.engine.stats()

    stats = asyncio.run(asyncio.wait_for(go(), timeout=90))
    path = tracefile.find_xplane(str(tmp_path))
    monkeypatch.setattr(ps, "xplane_of", lambda run: path)
    got = read_all({"cell": "cpu", "trace": [object()], "window_s": None})
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert got["wave_occupancy_pct.bulk"] == \
        pytest.approx(stats["mean_occupancy"] * 100)
    assert len(ps.window_spans(ps.events_of(path))[ps.STEP]) == \
        stats["invocations"] == len(payloads)
