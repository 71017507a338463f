"""Mean time per wave of the engine's runner call: ``logic.engine.launch``
(the fused runner called on the wave's slab, its host-to-device put
included, up to the returned array and the start of its copy back) over
the window's ``logic.engine.step`` spans, from the profiler's trace
(``launchspans.py``)."""
from benchmarks.chip import launchspans


def read(run):
    spans = launchspans.of_run(run)
    return None if spans is None else launchspans.launch_ms(spans)
