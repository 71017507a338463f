"""Mean time between waves: from the end of one ``logic.engine.step`` to
the start of the next, the waves of every executor thread in order of
start (the front door's loop and the hop to the executor), from the
profiler's trace (``programspans.py``)."""
from benchmarks.chip import programspans


def read(run):
    spans = programspans.of_run(run)
    return None if spans is None else programspans.gap_ms(spans)
