"""The engine's launch spans in a traced run, for the per-layer metrics
that read them.

``programspans.py`` reads the engine's waves and the phases its metrics
use; the runner call ``logic.engine.launch`` is not among them. This
module reads it beside the waves and the window markers, with its stats:
``in_bytes`` (the slab handed to the runner) and ``vmem_bytes`` (the
scoped VMEM the fabric launch asks for), which a program older than those
stats does not write. The spans are clipped to the window as
``programspans.window_spans`` does, and the reductions take plain
``{name: [(start, end, stats)]}`` so that tests feed them synthetic spans.
"""
from __future__ import annotations

import functools

from benchmarks.chip import programspans, tracefile

LAUNCH = "logic.engine.launch"
#: every span a reader here uses, and the window markers
NAMES = frozenset({LAUNCH, programspans.STEP, tracefile.OPEN,
                   tracefile.CLOSE})


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


def of_run(run) -> dict | None:
    """The window's launch and wave spans of a traced run; ``None``
    untraced."""
    if run["trace"] is None:
        return None
    return programspans.window_spans(
        events_of(programspans.xplane_of(run)), run["window_s"])


def launch_ms(spans: dict) -> float | None:
    """Mean time of the runner call per wave, in ms."""
    return programspans.phase_ms(spans, LAUNCH)


def gb_per_s(spans: dict) -> float | None:
    """Slab bytes handed to the runner over the time of the calls, in
    GB/s, over the launches that carry ``in_bytes``; ``None`` where none
    does."""
    got = [(st["in_bytes"], b - a) for a, b, st in spans.get(LAUNCH, [])
           if "in_bytes" in st]
    ns = sum(d for _, d in got)
    return sum(n for n, _ in got) / ns if ns > 0 else None
