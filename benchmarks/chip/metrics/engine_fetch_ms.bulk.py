"""Mean time per wave in which the engine waits for the device and copies
the wave's result to the host: ``logic.engine.fetch`` (``np.asarray`` of
the runner's array) over the window's ``logic.engine.step`` spans, from
the profiler's trace (``programspans.py``)."""
from benchmarks.chip import programspans


def read(run):
    spans = programspans.of_run(run)
    return None if spans is None else programspans.phase_ms(
        spans, programspans.FETCH)
