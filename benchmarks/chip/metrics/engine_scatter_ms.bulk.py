"""Mean time per wave of the engine's scatter: ``logic.engine.scatter``
(each request's rows of the result copied out, slots released, completed
requests retired) over the window's ``logic.engine.step`` spans, from the
profiler's trace (``programspans.py``)."""
from benchmarks.chip import programspans


def read(run):
    spans = programspans.of_run(run)
    return None if spans is None else programspans.phase_ms(
        spans, programspans.SCATTER)
