"""The metric readers' arithmetic against hand-computed numbers."""
import math

import numpy as np
import pytest

from bench_tiny import ROOT

from benchmarks.chip.netlist import load_module
from benchmarks.chip.tracefile import DeviceReduction

PEAKS = {"int32_ops_per_s": 1e12, "hbm_bytes_per_s": 1e10}


def reader(name):
    return load_module(ROOT / "benchmarks" / "chip" / "metrics"
                       / f"{name}.py").read


def red(kernel_count, kernel_ns, busy_ns=0.0, window_ns=1e9):
    return DeviceReduction("/device:TPU:0", window_ns, busy_ns=busy_ns,
                           kernel_ns=kernel_ns, kernel_count=kernel_count)


def base(**kw):
    run = {"gates": 1000, "n_inputs": 20, "n_outputs": 4, "capacity": 4096,
           "chips": 1, "window_s": 2.0, "t0": 0.0, "t_close": 2.0,
           "drain_s": 60.0, "waves": 3, "peaks": PEAKS, "trace": None,
           "n": np.asarray([1.6e6, 1.6e6, 5.0]),
           "due": np.asarray([0.1, 0.2, 0.3]),
           "submit": np.asarray([0.15, np.nan, 0.32]),
           "done": np.asarray([1.0, 1.9, np.nan]),
           "step_s": np.asarray([0.002, 0.004])}
    run.update(kw)
    return run


@pytest.mark.parametrize("chips", [1, 4])
def test_roofline_share(chips):
    # a launch on one device: 4096 samples = 128 words; ops = 1000 x 128;
    # bytes = (20 + 4) x 128 x 4 = 12288 -> 1.2288 us at 1e10 B/s, above
    # 0.128 us of ops at 1e12/s; 10 launches took 20 us -> 61.44 %
    run = base(chips=chips, capacity=4096 * chips,
               trace=[red(10, 2e4)] * chips)
    assert reader("logic_fabric_roofline.bulk")(run) == pytest.approx(61.44)


def test_roofline_compute_bound_and_silent_without_kernel():
    run = base(gates=10**6, trace=[red(1, 1e6)])
    # 1.28e8 ops / 1e12 = 128 us over 1 ms -> 12.8 %
    assert reader("logic_fabric_roofline.bulk")(run) == pytest.approx(12.8)
    assert reader("logic_fabric_roofline.bulk")(base(trace=[red(0, 0.0)])) \
        is None
    assert reader("logic_fabric_roofline.bulk")(base()) is None


def test_mfu():
    # samples completed by the close: 3.2e6; ops = 1000 x 3.2e6 / 32 = 1e8;
    # over 2 s x 1 chip x 1e12 -> 0.005 %
    assert reader("logic_mfu_pct.bulk")(base()) == pytest.approx(0.005)
    assert reader("logic_mfu_pct.bulk")(base(chips=4)) == \
        pytest.approx(0.00125)
    with pytest.raises(KeyError):
        reader("logic_mfu_pct.bulk")(base(peaks=None))


def test_samples_per_s_counts_completions_by_the_close():
    assert reader("samples_per_s")(base(t_close=1.5)) == \
        pytest.approx(1.6e6 / 2.0)
    assert reader("samples_per_s")(base()) == pytest.approx(3.2e6 / 2.0)


def test_host_span_metrics():
    run = base()
    assert reader("engine_step_ms.bulk")(run) == pytest.approx(3.0)
    assert reader("engine_step_ms.bulk")(base(step_s=np.zeros(0))) is None
    assert reader("setup_s")(base(setup_s=7.5)) == 7.5


def test_device_metrics():
    run = base(trace=[red(3, 3e6, busy_ns=2.5e8), red(3, 6e6, busy_ns=5e8)])
    assert reader("device_idle_pct.bulk")(run) == pytest.approx(62.5)
    assert reader("device_idle_pct.bulk")(base()) is None
    assert not math.isnan(reader("device_idle_pct.bulk")(run))
