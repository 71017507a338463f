"""The served path's profiler spans: a small ``FrontDoor`` -> ``LogicEngine``
run traced with ``jax.profiler`` on the CPU backend, read back from its
``.xplane.pb``."""
import asyncio
import glob

import jax
import numpy as np
import pytest

from repro.core.gate_ir import random_graph
from repro.core.spec import CompileSpec
from repro.serve import FrontDoor
from repro.serve.frontdoor import COMPLETE_SPAN, DISPATCH_SPAN, ROUTE_SPAN
from repro.serve.logic_engine import (ADMIT_SPAN, FETCH_SPAN, LAUNCH_SPAN,
                                      SCATTER_SPAN, SLAB_SPAN, STEP_SPAN)

PHASES = (ADMIT_SPAN, SLAB_SPAN, LAUNCH_SPAN, FETCH_SPAN, SCATTER_SPAN)
SIZES = (5, 40, 64, 100, 17, 3)       # 100 > capacity: two chunks


def _serve(graph, payloads):
    """Serve every payload through one front door, the first after a
    warm-up wave; returns the results in order."""
    async def go():
        door = FrontDoor(spec=CompileSpec(n_unit=16), capacity=64,
                         default_deadline_s=60.0)
        door.register("t", graph)
        async with door:
            await door.submit("t", payloads[0][:1])
            return await asyncio.gather(
                *(door.submit("t", bits) for bits in payloads)), door
    return asyncio.run(asyncio.wait_for(go(), timeout=90))


def _host_events(log_dir):
    """``[(name, start, end, stats)]`` of every host thread's ``logic.*``
    spans."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            out.extend((e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events
                       if e.name.startswith("logic."))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    rng = np.random.default_rng(5)
    graph = random_graph(rng, 10, 150, 6, locality=40)
    payloads = [rng.integers(0, 2, (n, graph.n_inputs)).astype(bool)
                for n in SIZES]
    untraced, _ = _serve(graph, payloads)
    log_dir = tmp_path_factory.mktemp("trace")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        results, door = _serve(graph, payloads)
    finally:
        jax.profiler.stop_trace()
    return graph, payloads, untraced, results, door, _host_events(log_dir)


def test_served_bits_equal_the_graph_traced_and_untraced(traced):
    graph, payloads, untraced, results, _, _ = traced
    for bits, a, b in zip(payloads, untraced, results):
        want = graph.evaluate(bits)
        assert (a == want).all() and (b == want).all()


def test_each_phase_once_per_wave_nested_in_order(traced):
    """A step holds the launched wave's admit, slab and launch, then the
    fetched wave's fetch and scatter; a step that only launches (its wave
    kept in flight) carries no wave stats."""
    *_, door, events = traced
    steps = [e for e in events if e[0] == STEP_SPAN]
    waves = door.engine.invocations
    assert sum("rows" in st for *_, st in steps) == waves >= 3
    for name in PHASES:
        assert sum(e[0] == name for e in events) == waves
    for _, lo, hi, st in steps:
        inside = sorted((s, e, n) for n, s, e, _ in events
                        if n in PHASES and lo <= s and e <= hi)
        want = ((list(PHASES), list(PHASES[3:])) if "rows" in st
                else (list(PHASES[:3]),))
        assert [n for _, _, n in inside] in want
        assert all(a[1] <= b[0] for a, b in zip(inside, inside[1:]))


def test_wave_stats_count_the_rows(traced):
    _, payloads, *_, door, events = traced
    stats = [st for n, _, _, st in events if n == STEP_SPAN and "rows" in st]
    assert len(stats) == door.engine.invocations
    assert all({"rows", "capacity", "chunks", "finished"} <= set(st)
               for st in stats)
    assert {st["capacity"] for st in stats} == {door.engine.capacity}
    # the warm-up request's one sample, then every payload's samples
    assert sum(st["rows"] for st in stats) == 1 + sum(SIZES)
    assert sum(st["finished"] for st in stats) == 1 + len(SIZES)
    assert sum(st["chunks"] for st in stats) == 1 + len(SIZES) + 1
    occupancy = (sum(st["rows"] for st in stats)
                 / sum(st["capacity"] for st in stats))
    assert occupancy == pytest.approx(door.engine.stats()["mean_occupancy"])


def test_dispatch_and_complete_carry_the_request_uid(traced):
    *_, events = traced
    dispatch = {st["uid"]: st for n, _, _, st in events
                if n == DISPATCH_SPAN}
    complete = {st["uid"]: st for n, _, _, st in events
                if n == COMPLETE_SPAN}
    assert len(dispatch) == len(complete) == 1 + len(SIZES)
    assert set(dispatch) == set(complete)
    assert sorted(st["samples"] for st in dispatch.values()) == \
        sorted((1,) + SIZES)
    assert all(st["queued_us"] >= 0 for st in dispatch.values())
    assert all(st["latency_us"] > 0 for st in complete.values())
    routes = [st for n, _, _, st in events if n == ROUTE_SPAN]
    assert sum(st["requests"] for st in routes) == 1 + len(SIZES)


def test_every_wave_says_whether_it_overlapped(traced):
    """The concurrent payloads queue more than a wave: some waves are
    fetched by a later step than launched them, and each wave's step
    says so in ``overlapped``, as the engine counts it."""
    *_, door, events = traced
    stats = [st for n, _, _, st in events if n == STEP_SPAN and "rows" in st]
    assert all(st["overlapped"] in (0, 1) for st in stats)
    overlapped = sum(st["overlapped"] for st in stats)
    assert overlapped == door.engine.stats()["overlapped_waves"] >= 1
