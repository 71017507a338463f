"""``wave_overlap_pct.bulk`` on synthetic ``logic.engine.step`` spans:
every wave overlapped, none, a mix clipped to the window, and a program
whose steps carry no ``overlapped`` stat."""
import pytest

from bench_tiny import ROOT

from benchmarks.chip import programspans as ps
from benchmarks.chip.netlist import load_module
from benchmarks.chip.tracefile import CLOSE, OPEN

MS = 1e6
read = load_module(ROOT / "benchmarks" / "chip" / "metrics"
                   / "wave_overlap_pct.bulk.py").read


def steps(flags, start=10.0):
    """A step span every 5 ms from ``start``; a flag of ``None`` is a step
    that only launched (no wave stats), ``"old"`` a wave of a program
    without the stat."""
    out = []
    for i, flag in enumerate(flags):
        stats = {}
        if flag is not None:
            stats = {"rows": 4096, "capacity": 4096, "chunks": 1,
                     "finished": 1}
            if flag != "old":
                stats["overlapped"] = flag
        out.append((ps.STEP, (start + 5 * i) * MS, 4 * MS, stats))
    return out


def traced_run(monkeypatch, events):
    markers = [(OPEN, 0.0, 0.0, {}), (CLOSE, 100 * MS, 0.0, {})]
    monkeypatch.setattr(ps, "xplane_of", lambda run: "synthetic.xplane.pb")
    monkeypatch.setattr(ps, "events_of", lambda path: markers + events)
    return {"cell": "lenet5-head.bulk", "trace": [object()],
            "window_s": None}


@pytest.mark.parametrize("flags, want", [
    ([None, 1, 1, 1, 1], 100.0),            # deep queue: every wave
    ([0, 0, 0], 0.0),                       # lone requests: none
    ([0, None, 1, 1, 0], 50.0),             # a burst between lone waves
])
def test_share_of_waves_overlapped(monkeypatch, flags, want):
    assert read(traced_run(monkeypatch, steps(flags))) == \
        pytest.approx(want)


def test_steps_outside_the_window_do_not_count(monkeypatch):
    events = steps([0, 0], start=-20.0) + steps([1, 1, 1])
    assert read(traced_run(monkeypatch, events)) == pytest.approx(100.0)


def test_silent_without_the_stat_or_untraced(monkeypatch):
    assert read(traced_run(monkeypatch, steps(["old", "old", None]))) is None
    assert read(traced_run(monkeypatch, [])) is None
    assert read({"cell": "lenet5-head.bulk", "trace": None,
                 "window_s": 51.0}) is None
