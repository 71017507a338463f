"""The ``logic_fabric`` kernel's share of its roofline: the least time the
chip could take for the launches' work over their summed device time, per
device, averaged over the devices.

Work of one launch on one device, at its ``capacity / chips`` samples:
one int32 op per gate of the netlist as registered with the front door per
32-sample word, so the same work whatever implements it; bytes are the
input and output words. The least time is the larger of ops over the peak
int32 rate and bytes over HBM bandwidth (``peaks.json``). A wave that is
not full still launches every word of its capacity, so its padding rows
count as the kernel's work here; ``logic_mfu_pct.bulk`` counts only the
samples served.
"""
import numpy as np


def work(run):
    """(int32 ops, bytes) of one launch on one device."""
    words = run["capacity"] // run["chips"] // 32
    ops = run["gates"] * words
    nbytes = (run["n_inputs"] + run["n_outputs"]) * words * 4
    return ops, nbytes


def read(run):
    reds = run["trace"]
    if not reds or not any(r.kernel_count for r in reds):
        return None
    peak = run["peaks"]
    if peak is None:
        raise KeyError("the device kind is not in peaks.json")
    ops, nbytes = work(run)
    least = max(ops / peak["int32_ops_per_s"],
                nbytes / peak["hbm_bytes_per_s"])
    shares = [least * r.kernel_count / (r.kernel_ns / 1e9) * 100
              for r in reds if r.kernel_count]
    return float(np.mean(shares))
