"""The program's own spans in a traced run, for the per-layer metrics that
read them.

``repro.serve`` records its layers as ``jax.profiler.TraceAnnotation``
host spans: each engine wave is ``logic.engine.step`` (stats ``rows``,
``capacity``, ``chunks``, ``finished``) with the phases ``admit``,
``slab``, ``launch``, ``fetch`` and ``scatter`` nested in it; the front
door adds ``logic.frontdoor.dispatch``, ``route`` and ``complete``. This
module finds the traced run's ``.xplane.pb`` where the harness writes it,
reads those spans with their stats (``tracefile.planes_of`` drops stats),
and clips them to the window the way the trace reduction does: from the
``bench.window.open`` marker for the window's seconds.

The span names are written out here, not imported from the program: a
renamed span then reads as missing (``None``), and the metric falls
silent instead of following the rename. As in ``tracefile.py``, reading
the file (:func:`events_of`, memoised per file) is apart from the
reductions, which take plain ``(name, start_ns, dur_ns, stats)`` tuples
so that tests feed them synthetic spans.
"""
from __future__ import annotations

import functools

from benchmarks.chip import tracefile
from benchmarks.chip.cells import ROOT, bench_dir

STEP = "logic.engine.step"
SLAB = "logic.engine.slab"
FETCH = "logic.engine.fetch"
SCATTER = "logic.engine.scatter"
#: every span a reader here uses, and the window markers
NAMES = frozenset({STEP, SLAB, FETCH, SCATTER, tracefile.OPEN,
                   tracefile.CLOSE})


def xplane_of(run) -> str:
    """The traced run's profile, where the harness writes it."""
    return tracefile.find_xplane(
        str(bench_dir(ROOT) / ".cache" / "trace" / run["cell"]))


@functools.lru_cache(maxsize=2)
def events_of(path: str) -> tuple:
    """``(name, start_ns, dur_ns, stats)`` of the host events in
    :data:`NAMES`, all host threads together."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in NAMES:
                    out.append((e.name, float(e.start_ns),
                                float(e.duration_ns), dict(e.stats)))
    return tuple(out)


def window_spans(events, window_s: float | None = None) -> dict:
    """``{name: [(start, end, stats)]}`` of the spans that overlap the
    window, each clipped to it, in order of start."""
    markers = [(s, s + d, n) for n, s, d, _ in events
               if n in (tracefile.OPEN, tracefile.CLOSE)]
    lo, hi = tracefile.window_of(markers, window_s)
    out: dict = {}
    for n, s, d, stats in sorted(events, key=lambda ev: ev[1]):
        a, b = max(s, lo), min(s + d, hi)
        if n not in (tracefile.OPEN, tracefile.CLOSE) and b > a:
            out.setdefault(n, []).append((a, b, stats))
    return out


def of_run(run) -> dict | None:
    """The window's program spans of a traced run; ``None`` untraced."""
    if run["trace"] is None:
        return None
    return window_spans(events_of(xplane_of(run)), run["window_s"])


def phase_ms(spans: dict, name: str) -> float | None:
    """Mean time of the phase ``name`` per wave (``logic.engine.step``),
    in ms; ``None`` where either span is missing."""
    if not spans.get(STEP) or not spans.get(name):
        return None
    return sum(b - a for a, b, _ in spans[name]) / len(spans[STEP]) / 1e6


def gap_ms(spans: dict) -> float | None:
    """Mean time from the end of one wave to the start of the next, in
    ms, with the waves of every thread in order of start."""
    steps = spans.get(STEP, [])
    if len(steps) < 2:
        return None
    gaps = [nxt[0] - cur[1] for cur, nxt in zip(steps, steps[1:])]
    return sum(gaps) / len(gaps) / 1e6


def occupancy_pct(spans: dict) -> float | None:
    """Samples admitted over rows launched, in %, over the window's waves
    (the ``rows`` and ``capacity`` stats of ``logic.engine.step``)."""
    steps = [st for _, _, st in spans.get(STEP, [])
             if "rows" in st and "capacity" in st]
    if not steps:
        return None
    return (sum(st["rows"] for st in steps)
            / sum(st["capacity"] for st in steps) * 100)
