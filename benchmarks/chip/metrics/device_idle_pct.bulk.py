"""Share of the traced window in which no op ran on the device, per
device, averaged over the devices (``tracefile.py``)."""
import numpy as np


def read(run):
    reds = run["trace"]
    if not reds:
        return None
    return float(np.mean([1.0 - r.busy_ns / r.window_ns for r in reds])) * 100
