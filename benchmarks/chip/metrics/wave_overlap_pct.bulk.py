"""Share of the window's waves whose result-to-host round trip overlapped
host work, in %: the ``overlapped`` stat (1 when the engine launched the
wave in an earlier call of ``step`` than the one that fetched it) over the
``logic.engine.step`` spans that carry it, from the profiler's trace
(``programspans.py``). A program without the stat reads ``None``."""
from benchmarks.chip import programspans


def read(run):
    spans = programspans.of_run(run)
    if spans is None:
        return None
    flags = [st["overlapped"] for _, _, st in spans.get(programspans.STEP, [])
             if "overlapped" in st]
    return sum(flags) / len(flags) * 100 if flags else None
