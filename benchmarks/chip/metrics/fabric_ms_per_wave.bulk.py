"""Device time of one ``logic_fabric`` kernel launch, in ms: the kernel's
summed device time in the traced window over its number of launches, per
device, averaged over the devices (``tracefile.py``)."""
import numpy as np


def read(run):
    reds = [r for r in run["trace"] or () if r.kernel_count]
    if not reds:
        return None
    return float(np.mean([r.kernel_ns / r.kernel_count for r in reds])) / 1e6
