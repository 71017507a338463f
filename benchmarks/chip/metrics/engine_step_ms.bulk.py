"""Mean host-clock time of ``LogicEngine.step`` over the window's waves,
the block on the wave's result included (``spans.TracedEngine``)."""
import numpy as np


def read(run):
    s = run["step_s"]
    return float(np.mean(s)) * 1e3 if s.size else None
