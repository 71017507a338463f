"""Mean time of the engine's slab build per wave: ``logic.engine.slab``
(``np.zeros`` of the wave's input and the row copies into it) over the
window's ``logic.engine.step`` spans, from the profiler's trace
(``programspans.py``)."""
from benchmarks.chip import programspans


def read(run):
    spans = programspans.of_run(run)
    return None if spans is None else programspans.phase_ms(
        spans, programspans.SLAB)
