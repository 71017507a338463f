"""The benchmark's load generator: seeded, the same work for every seed,
open loops timed from the due time, closed loops with fixed clients."""
import asyncio
import time

import numpy as np
import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on sys.path)

from benchmarks.chip import loadgen
from benchmarks.chip.spans import Spans

OPEN = {"loop": "open", "rate_rps": 500,
        "size": {"dist": "geometric", "mean": 8, "max": 64},
        "deadline_s": 10.0, "check_share": 0.1, "pool_rows": 256}
CLOSED = {"loop": "closed", "clients": 4,
          "size": {"dist": "uniform", "min": 1, "max": 8, "unit": 16},
          "deadline_s": 10.0, "check_share": 0.1, "pool_rows": 256}
BIG_SEED = 2**31 + 7


@pytest.mark.parametrize("traffic", [OPEN, CLOSED], ids=["open", "closed"])
def test_schedule_is_deterministic_per_seed(traffic):
    a = loadgen.make_schedule(traffic, BIG_SEED, 2.0, 12)
    b = loadgen.make_schedule(traffic, BIG_SEED, 2.0, 12)
    c = loadgen.make_schedule(traffic, BIG_SEED + 1, 2.0, 12)
    for f in ("sizes", "offsets", "keep", "pool"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.sizes, c.sizes)
    # another seed reorders the same work
    np.testing.assert_array_equal(np.sort(a.sizes), np.sort(c.sizes))
    if traffic["loop"] == "open":
        q = loadgen.gap_quantiles(traffic["rate_rps"], 1000)
        for s in (a, c):
            d = np.diff(s.due)
            j = np.clip(np.searchsorted(q, d), 1, len(q) - 1)
            near = np.minimum(abs(q[j] - d), abs(q[j - 1] - d))
            assert near.max() < 1e-9        # every gap is one of the set
        assert len(a.due) == len(c.due) == 1000     # rate x seconds
    assert a.pool.dtype == bool and a.pool.shape == (256, 12)
    assert ((a.offsets + a.sizes) <= 256).all()


def test_size_quantiles():
    u = loadgen.size_quantiles({"dist": "uniform", "min": 1, "max": 8,
                                "unit": 1024})
    assert u.min() == 1024 and u.max() == 8192
    assert np.all(np.isin(u // 1024, np.arange(1, 9)))
    g = loadgen.size_quantiles({"dist": "geometric", "mean": 8, "max": 64})
    assert g.min() == 1 and g.max() == 64
    assert 7.0 < g.mean() < 8.5
    gaps = loadgen.gap_quantiles(100.0, 4096)
    assert abs(gaps.mean() - 0.01) < 2e-4


class FakeEngine:
    def __init__(self):
        self.submit_t = {}


class FakeDoor:
    """Answers every request after ``service_s``; the first request's
    admission blocks the event loop for ``stall_s`` (a stalled host)."""

    def __init__(self, engine, service_s=0.001, stall_s=0.0):
        self.engine, self.service_s, self.stall_s = engine, service_s, stall_s
        self.inflight = self.max_inflight = self.calls = 0

    async def submit(self, tenant, bits, deadline_s=None):
        self.calls += 1
        if self.calls == 1 and self.stall_s:
            time.sleep(self.stall_s)
        self.engine.submit_t[id(bits)] = time.perf_counter()
        self.inflight += 1
        self.max_inflight = max(self.max_inflight, self.inflight)
        await asyncio.sleep(self.service_s)
        self.inflight -= 1
        return np.zeros((bits.shape[0], 1), bool)


def test_open_loop_times_from_the_due_time():
    sched = loadgen.make_schedule(OPEN, 3, 0.4, 12)
    eng = FakeEngine()
    door = FakeDoor(eng, stall_s=0.1)
    out = asyncio.run(loadgen.drive(door, "t", sched, OPEN, 0.4, eng,
                                    Spans(False)))
    due = np.asarray(out.due) - out.t0
    lat = np.asarray(out.done) - np.asarray(out.due)
    late = np.asarray(out.sent) - np.asarray(out.due)
    assert out.attempted == 200 and not out.failed
    # requests due during the 100 ms stall went out late, and their
    # latency counts the wait from when they were due
    stalled = (due > 0.005) & (due < 0.09)
    assert stalled.any()
    assert (late[stalled] > 0.01).all()
    assert (lat[stalled] >= late[stalled]).all()
    assert lat[stalled].min() > 0.01
    assert np.isfinite(np.asarray(out.submit)).all()


def test_closed_loop_keeps_a_fixed_number_of_clients():
    sched = loadgen.make_schedule(CLOSED, 3, 0.3, 12)
    eng = FakeEngine()
    door = FakeDoor(eng, service_s=0.002)
    out = asyncio.run(loadgen.drive(door, "t", sched, CLOSED, 0.3, eng,
                                    Spans(False)))
    assert door.max_inflight == CLOSED["clients"]
    assert out.attempted > 4 * CLOSED["clients"]
    assert not np.isnan(out.done).any()
    # each client sends its next request only after the last completes
    assert out.index == sorted(out.index)
    assert out.kept and out.longest[1] >= 0
