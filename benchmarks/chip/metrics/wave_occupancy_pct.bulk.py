"""Useful rows over rows launched: the ``rows`` stat summed over the
window's ``logic.engine.step`` spans, over their summed ``capacity``, in
%, from the profiler's trace (``programspans.py``)."""
from benchmarks.chip import programspans


def read(run):
    spans = programspans.of_run(run)
    return None if spans is None else programspans.occupancy_pct(spans)
