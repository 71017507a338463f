"""Bytes of input slab the engine hands its runner per second of runner
call: the ``in_bytes`` stat summed over the window's
``logic.engine.launch`` spans, over their summed time, in GB/s, from the
profiler's trace (``launchspans.py``). A program without the stat reads
``None``."""
from benchmarks.chip import launchspans


def read(run):
    spans = launchspans.of_run(run)
    return None if spans is None else launchspans.gb_per_s(spans)
