"""Load generator of the benchmark: a corrected copy of
``repro.serve.traffic.run_trace``.

What changed against that copy, and why:

* an open loop times each request from the moment it was due, not from
  when its task started, so a stall that delays later sends shows in their
  latency; how late each send went out is recorded;
* payloads are drawn before the send loop (one seeded pool of input rows;
  a request is a slice of it at a seeded offset), so drawing them costs
  nothing inside the window;
* every seed gets the same set of request sizes and inter-arrival gaps,
  in another order (quantiles of the distribution, permuted by the seed),
  so the seed changes which bits and which order, not how much work;
* a closed loop keeps a fixed number of clients, each sending its next
  request when its last one completes.

A traffic file (``traffic/<mix>.json``) gives the parameters:

  loop         "closed" (with "clients") or "open" (with "rate_rps")
  size         {"dist": "uniform", "min", "max", "unit"} or
               {"dist": "geometric", "mean", "max"}, in samples x unit
  deadline_s   the per-request deadline handed to the front door
  check_share  share of requests whose answers are kept and compared
  pool_rows    rows in the seeded pool of input bits
  max_queue    the front door's admission queue bound
"""
from __future__ import annotations

import asyncio
import itertools
import time
from dataclasses import dataclass, field

import numpy as np

from benchmarks.chip.spans import COMPLETE, SEND

#: distinct request sizes (and gaps) per seed, permuted and cycled
N_QUANTILES = 4096
#: how long past the window's close an answer is waited for
DRAIN_S = 60.0


def size_quantiles(size: dict, n: int = N_QUANTILES) -> np.ndarray:
    """The fixed set of ``n`` request sizes (in samples) of a mix."""
    q = (np.arange(n) + 0.5) / n
    unit = int(size.get("unit", 1))
    if size["dist"] == "uniform":
        lo, hi = int(size["min"]), int(size["max"])
        units = lo + np.floor(q * (hi - lo + 1)).astype(np.int64)
    elif size["dist"] == "geometric":
        p = 1.0 / float(size["mean"])
        units = np.ceil(np.log1p(-q) / np.log1p(-p)).astype(np.int64)
        units = np.clip(units, 1, int(size["max"]))
    else:
        raise ValueError(f"unknown size distribution {size['dist']!r}")
    return units * unit


def gap_quantiles(rate_rps: float, n: int) -> np.ndarray:
    """``n`` exponential inter-arrival gaps (s) at ``rate_rps``, by
    quantile: a Poisson process's gaps, the same set for every seed."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate_rps


@dataclass
class Schedule:
    """Everything a run sends, drawn from the seed before the window."""

    sizes: np.ndarray            # samples per request, in send order
    offsets: np.ndarray          # pool row of each request's first sample
    keep: np.ndarray             # bool: answer kept for the comparison
    pool: np.ndarray             # (pool_rows, n_inputs) bool
    due: np.ndarray | None       # open loop: due offsets (s) from t0

    def payload(self, i: int) -> np.ndarray:
        j = i % len(self.sizes)
        o = int(self.offsets[j])
        return self.pool[o:o + int(self.sizes[j])]


def make_schedule(traffic: dict, seed: int, seconds: float,
                  n_inputs: int) -> Schedule:
    rng = np.random.default_rng(seed)
    base = size_quantiles(traffic["size"])
    pool_rows = int(traffic["pool_rows"])
    if base.max() > pool_rows:
        raise ValueError(f"pool_rows {pool_rows} below the largest "
                         f"request ({base.max()} samples)")
    due = None
    if traffic["loop"] == "open":
        n = max(1, int(round(float(traffic["rate_rps"]) * seconds)))
        sizes = rng.permutation(np.resize(base, n))
        gaps = rng.permutation(gap_quantiles(float(traffic["rate_rps"]), n))
        due = np.cumsum(gaps) - gaps[0]
    elif traffic["loop"] == "closed":
        sizes = rng.permutation(base)
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    offsets = rng.integers(0, pool_rows - sizes + 1)
    keep = rng.random(len(sizes)) < float(traffic["check_share"])
    raw = rng.integers(0, 256, (pool_rows, -(-n_inputs // 8)), dtype=np.uint8)
    pool = np.unpackbits(raw, axis=1, count=n_inputs).astype(bool)
    return Schedule(sizes, offsets, keep, pool, due)


@dataclass
class Outcome:
    """Per-request records of one window (host clock, perf_counter)."""

    t0: float = 0.0
    t_close: float = 0.0
    index: list = field(default_factory=list)      # request i, send order
    n: list = field(default_factory=list)
    due: list = field(default_factory=list)        # due (open) / sent
    sent: list = field(default_factory=list)
    submit: list = field(default_factory=list)     # door -> engine.submit
    done: list = field(default_factory=list)       # nan: no answer
    failed: dict = field(default_factory=dict)     # i -> shed code / error
    kept: dict = field(default_factory=dict)       # i -> served bits
    longest: tuple = (-1, -1, None)                # (n, i, bits)

    @property
    def attempted(self) -> int:
        return len(self.index)


async def _send(door, tenant, sched, deadline_s, i, due, out, engine,
                 spans):
    """Send request ``i`` and record its outcome (one row of ``out``)."""
    from repro.serve.frontdoor import RequestRejected

    payload = sched.payload(i)
    row = len(out.index)
    out.index.append(i)
    out.n.append(payload.shape[0])
    out.due.append(due)
    out.sent.append(time.perf_counter())
    out.submit.append(float("nan"))
    out.done.append(float("nan"))
    try:
        with spans(SEND):
            task = asyncio.Task(door.submit(tenant, payload,
                                            deadline_s=deadline_s),
                                loop=asyncio.get_running_loop(),
                                eager_start=True)
        bits = await task
    except RequestRejected as exc:
        out.failed[i] = exc.reason.code
        return
    except Exception as exc:          # noqa: BLE001 — a failed wave is a
        out.failed[i] = repr(exc)     # failed request, recorded not raised
        return
    finally:
        out.submit[row] = engine.submit_t.pop(id(payload), float("nan"))
    with spans(COMPLETE):
        out.done[row] = time.perf_counter()
        if sched.keep[i % len(sched.keep)]:
            out.kept[i] = bits
        if bits.shape[0] > out.longest[0]:
            out.longest = (bits.shape[0], i, bits)


async def closed_loop(door, tenant, sched, traffic, seconds, engine, spans
                      ) -> Outcome:
    """``clients`` clients, each sending its next request when the last
    completes, until the window closes; then every answer is awaited."""
    out = Outcome()
    cursor = itertools.count()
    deadline_s = float(traffic["deadline_s"])

    async def client():
        while time.perf_counter() < out.t_close:
            i = next(cursor)
            await _send(door, tenant, sched, deadline_s, i,
                         time.perf_counter(), out, engine, spans)

    out.t0 = time.perf_counter()
    out.t_close = out.t0 + seconds
    tasks = [asyncio.create_task(client())
             for _ in range(int(traffic["clients"]))]
    await _drain(tasks, out)
    return out


async def open_loop(door, tenant, sched, traffic, seconds, engine, spans
                    ) -> Outcome:
    """Every request of the schedule sent at its due time, whether or not
    earlier ones have completed; latency counts from the due time."""
    out = Outcome()
    deadline_s = float(traffic["deadline_s"])
    tasks = []
    out.t0 = time.perf_counter()
    out.t_close = out.t0 + seconds
    for i, d in enumerate(sched.due):
        due = out.t0 + float(d)
        if due >= out.t_close:
            break
        delay = due - time.perf_counter()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(
            _send(door, tenant, sched, deadline_s, i, due, out, engine,
                   spans)))
    await _drain(tasks, out)
    return out


async def _drain(tasks, out: Outcome) -> None:
    """Wait for every request until ``DRAIN_S`` past the close; one never
    answered keeps a nan ``done`` and counts as unanswered."""
    wait = max(0.0, out.t_close + DRAIN_S - time.perf_counter())
    _, pending = await asyncio.wait(tasks, timeout=wait) if tasks else \
        (set(), set())
    for t in pending:
        t.cancel()
    if pending:
        await asyncio.wait(pending)


async def drive(door, tenant, sched, traffic, seconds, engine, spans
                ) -> Outcome:
    loop = closed_loop if traffic["loop"] == "closed" else open_loop
    return await loop(door, tenant, sched, traffic, seconds, engine, spans)
