"""LogicEngine serving: cache, slot recycling, parity, partitions, shards."""
import subprocess
import sys

import numpy as np
import pytest

from repro.core.gate_ir import random_graph
from repro.core.scheduler import compile_graph
from repro.core.spec import CompileSpec
from repro.kernels.logic_dsp import logic_infer_bits
from repro.serve import LogicEngine, ProgramCache, SlotTable


def _graph(rng, n_in=12, n_gates=300, n_out=10):
    return random_graph(rng, n_in, n_gates, n_out, locality=48)


# ---------------------------------------------------------------------------
# program registry
# ---------------------------------------------------------------------------

def test_program_cache_hit_on_structural_copy(rng):
    """Keyed by structure: a renamed copy reuses the compiled program."""
    g = _graph(rng)
    eng = LogicEngine(CompileSpec(n_unit=16), capacity=64)
    X = rng.integers(0, 2, (20, g.n_inputs)).astype(bool)
    eng.serve(g, X)
    assert (eng.cache.hits, eng.cache.misses) == (0, 1)
    g2 = g.copy()
    g2.name = "same-structure-different-name"
    assert g2.fingerprint() == g.fingerprint()
    out = eng.serve(g2, X)
    assert eng.cache.misses == 1 and eng.cache.hits >= 1
    assert (out == g.evaluate(X)).all()


def test_program_cache_miss_on_structure_change(rng):
    g = _graph(rng)
    g2 = g.copy()
    g2.set_outputs(list(reversed(g2.outputs)))
    assert g.fingerprint() != g2.fingerprint()
    cache = ProgramCache()
    cache.get(g, CompileSpec(n_unit=16))
    cache.get(g2, CompileSpec(n_unit=16))
    cache.get(g, CompileSpec(n_unit=32))            # same graph, different fabric width
    assert cache.misses == 3 and cache.hits == 0
    cache.get(g, CompileSpec(n_unit=16))
    assert cache.hits == 1


def test_program_cache_lru_eviction(rng):
    cache = ProgramCache(max_entries=2)
    graphs = [_graph(rng, n_gates=60 + i) for i in range(3)]
    for g in graphs:
        cache.get(g, CompileSpec(n_unit=8))
    assert len(cache) == 2
    # oldest entry (graphs[0]) was evicted; re-fetch recompiles
    cache.get(graphs[0], CompileSpec(n_unit=8))
    assert cache.misses == 4


def test_program_cache_stats_schema(rng):
    """The stats dict is a pinned schema (dashboards + warm-start tests
    key on it): store counters are present — and zero — with no store
    attached, and compiles tracks actual facade invocations."""
    cache = ProgramCache()
    g = _graph(rng)
    cache.get(g, CompileSpec(n_unit=16))
    cache.get(g, CompileSpec(n_unit=16))
    assert cache.stats() == {
        "entries": 1, "hits": 1, "misses": 1, "compiles": 1,
        "compile_failures": 0, "store_hits": 0, "store_misses": 0,
        "store_failures": 0, "store_saves": 0, "store_save_failures": 0,
        "verifies": 0, "verify_failures": 0,
        "programs": 1}
    assert cache.store is None


def test_unbinding_budget_shares_monolithic_entry(rng):
    """Budgets the graph fits under normalize to the no-budget key."""
    g = _graph(rng, n_gates=80)
    cache = ProgramCache()
    # optimize="none": normalization must see the 80 raw gates (the
    # default pipeline would shrink the graph under the binding budget)
    spec = CompileSpec(n_unit=8, optimize="none")
    cache.get(g, spec)
    cache.get(g, spec.with_(max_gates=400))   # 80 <= 400: same mono program
    cache.get(g, spec.with_(max_gates=10 ** 6))
    assert cache.misses == 1 and cache.hits == 2
    cache.get(g, spec.with_(max_gates=30))    # binding budget: new pipeline
    assert cache.misses == 2


def test_max_retained_bounds_unclaimed_results(rng):
    """Fire-and-forget traffic cannot grow _requests without bound."""
    g = _graph(rng, n_in=6, n_gates=40, n_out=4)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32, max_retained=2)
    uids = []
    for _ in range(5):
        uids.append(eng.submit(g, rng.integers(0, 2, (4, 6)).astype(bool)))
        eng.drain()                       # fire and forget: never claimed
    assert len(eng._requests) == 2        # only the 2 newest retained
    with pytest.raises(KeyError):
        eng.result(uids[0])               # oldest was dropped
    assert eng.result(uids[-1]).shape == (4, 4)


def test_claimed_results_leave_retention_window(rng):
    """Claiming a result frees its retention slot and its bookkeeping:
    max_retained bounds UNCLAIMED results only, and a steady
    submit/drain/claim loop leaves no residue behind."""
    g = _graph(rng, n_in=6, n_gates=40, n_out=4)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32, max_retained=2)
    u0 = eng.submit(g, rng.integers(0, 2, (4, 6)).astype(bool))
    eng.drain()
    u1 = eng.submit(g, rng.integers(0, 2, (4, 6)).astype(bool))
    eng.drain()
    eng.result(u1)                        # claim the NEWEST
    u2 = eng.submit(g, rng.integers(0, 2, (4, 6)).astype(bool))
    eng.drain()
    assert eng.result(u0).shape == (4, 4)  # u0 survived: only 2 unclaimed
    eng.result(u2)
    assert not eng._requests and not eng._finished_order  # no residue


def test_shared_cache_rejects_max_programs(rng):
    with pytest.raises(ValueError):
        LogicEngine(cache=ProgramCache(), max_programs=4)


def test_eviction_with_queued_requests_recovers(rng):
    """An LRU-evicted program recompiles from the retained graph; queued
    requests complete instead of wedging the engine."""
    g1 = _graph(rng, n_gates=80)
    g2 = _graph(rng, n_gates=90)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32, max_programs=1)
    X1 = rng.integers(0, 2, (10, g1.n_inputs)).astype(bool)
    X2 = rng.integers(0, 2, (10, g2.n_inputs)).astype(bool)
    u1 = eng.submit(g1, X1)
    u2 = eng.submit(g2, X2)          # compiles g2, evicting g1's entry
    assert len(eng.cache) == 1
    eng.drain()
    assert (eng.result(u1) == g1.evaluate(X1)).all()
    assert (eng.result(u2) == g2.evaluate(X2)).all()
    assert eng.cache.misses >= 3     # g1, g2, then g1's recompile


def test_shared_cache_engines_keep_their_own_runners(rng):
    """Engines sharing a ProgramCache must not run each other's traces:
    runner config (backend/capacity/shard) is part of the runner key."""
    g = _graph(rng)
    cache = ProgramCache()
    a = LogicEngine(CompileSpec(n_unit=16), capacity=32, use_ref=True,
                    cache=cache)
    b = LogicEngine(CompileSpec(n_unit=16), capacity=64, shard=True,
                    cache=cache)
    X = rng.integers(0, 2, (20, g.n_inputs)).astype(bool)
    assert (a.serve(g, X) == g.evaluate(X)).all()
    assert (b.serve(g, X) == g.evaluate(X)).all()    # cache hit, own runner
    assert cache.misses == 1 and cache.hits >= 1
    # fetch the entry the engines shared: keyed on the POST-optimization
    # fingerprint, so the lookup goes through the same pass pipeline
    entry = cache.get(g, a.spec)
    assert len(entry.runners) == 2                   # one trace per config


# ---------------------------------------------------------------------------
# parity vs direct execution
# ---------------------------------------------------------------------------

def test_engine_parity_vs_logic_infer_bits(rng):
    """Batched serving == direct fused kernel call, bit for bit."""
    g = _graph(rng)
    prog = compile_graph(g, CompileSpec(n_unit=16))
    eng = LogicEngine(CompileSpec(n_unit=16), capacity=96)
    for n in (1, 31, 32, 37, 96):        # ragged and word-aligned sizes
        X = rng.integers(0, 2, (n, g.n_inputs)).astype(bool)
        got = eng.serve(g, X)
        assert got.shape == (n, g.n_outputs)
        assert (got == logic_infer_bits(prog, X)).all()
        assert (got == g.evaluate(X)).all()
    # every serve after the first hit the program cache
    assert eng.cache.misses == 1


def test_engine_parity_on_cached_path(rng):
    """Second serve (cache hit, warm jit) stays exact."""
    g = _graph(rng)
    eng = LogicEngine(CompileSpec(n_unit=16), capacity=64)
    X1 = rng.integers(0, 2, (40, g.n_inputs)).astype(bool)
    X2 = rng.integers(0, 2, (64, g.n_inputs)).astype(bool)
    eng.serve(g, X1)
    assert (eng.serve(g, X2) == g.evaluate(X2)).all()
    assert eng.cache.hits >= 1


def test_gateless_graph_served(rng):
    """0-step programs route through the jnp reference inside the engine."""
    from repro.core.gate_ir import LogicGraph
    g = LogicGraph(4, name="wires-only")
    g.set_outputs([2, 5, 3])
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32)
    X = rng.integers(0, 2, (11, 4)).astype(bool)
    assert (eng.serve(g, X) == g.evaluate(X)).all()


# ---------------------------------------------------------------------------
# slot batching / recycling
# ---------------------------------------------------------------------------

def test_slot_table_acquire_release_recycles():
    t = SlotTable(8)
    r1 = t.acquire(5)
    assert t.n_free == 3 and t.high_water == 5
    assert t.acquire(4) is None          # insufficient free rows
    r2 = t.acquire(3)
    assert t.n_free == 0 and t.high_water == 8
    t.release(r1)
    r3 = t.acquire(5)                    # recycled rows come back
    assert sorted(np.concatenate([r2, r3]).tolist()) == list(range(8))
    t.release(r2)
    t.release(r3)
    assert t.n_free == 8
    with pytest.raises(RuntimeError):    # partial double-release is caught
        t.release(r3)


def test_slot_recycling_ragged_requests(rng):
    """Ragged sizes (not multiples of 32) pack together and recycle slots."""
    g = _graph(rng, n_in=8, n_gates=120, n_out=6)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=64)
    sizes = [40, 33, 10, 64, 1, 17]      # crosses word boundaries freely
    uids = [eng.submit(g, rng.integers(0, 2, (n, 8)).astype(bool))
            for n in sizes]
    waves = 0
    while not eng.idle:
        eng.step()
        waves += 1
        assert waves < 20
    assert eng.invocations >= 2          # couldn't fit in one wave
    assert eng.samples_served == sum(sizes)
    assert eng.slots.n_free == eng.capacity          # everything recycled
    for uid, n in zip(uids, sizes):
        req = eng._requests[uid]
        assert req.done
        assert (eng.result(uid) ==
                g.evaluate(req.inputs)).all()
    # first wave packed multiple ragged requests into one invocation
    assert eng.stats()["slot_high_water"] > max(sizes[:3])


def test_oversized_request_chunks(rng):
    """Requests above capacity split into waves but return one result."""
    g = _graph(rng, n_in=8, n_gates=100, n_out=5)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32)
    X = rng.integers(0, 2, (150, 8)).astype(bool)
    out = eng.serve(g, X)
    assert out.shape == (150, 5)
    assert (out == g.evaluate(X)).all()
    assert eng.invocations >= 5


def test_empty_request_completes_immediately(rng):
    g = _graph(rng, n_in=6, n_gates=40, n_out=4)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32)
    uid = eng.submit(g, np.zeros((0, 6), dtype=bool))
    assert eng.idle
    assert eng.result(uid).shape == (0, 4)


def test_mixed_graph_queues_serve_fifo(rng):
    """Two different graphs queued at once both complete correctly."""
    ga = _graph(rng, n_in=8, n_gates=90, n_out=5)
    gb = _graph(rng, n_in=11, n_gates=140, n_out=7)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=64)
    Xa = rng.integers(0, 2, (21, 8)).astype(bool)
    Xb = rng.integers(0, 2, (50, 11)).astype(bool)
    ua, ub = eng.submit(ga, Xa), eng.submit(gb, Xb)
    eng.drain()
    assert (eng.result(ua) == ga.evaluate(Xa)).all()
    assert (eng.result(ub) == gb.evaluate(Xb)).all()
    assert len(eng.cache) == 2


# ---------------------------------------------------------------------------
# partitioned serving
# ---------------------------------------------------------------------------

def test_partitioned_serving_equivalence(rng):
    """Pipelined multi-program serving == monolithic, bit for bit."""
    g = random_graph(rng, 12, 400, 20, locality=48)
    eng = LogicEngine(CompileSpec(n_unit=16, max_gates=150), capacity=96)
    # fetch the entry the engine serves (post-optimization key)
    entry = eng.cache.get(g, eng.spec)
    assert len(entry.programs) >= 2      # actually partitioned
    X = rng.integers(0, 2, (70, 12)).astype(bool)
    got = eng.serve(g, X)
    assert (got == g.evaluate(X)).all()
    mono = compile_graph(g, CompileSpec(n_unit=16))
    assert (got == logic_infer_bits(mono, X)).all()
    # partitioning shrank the per-program buffer budget (the point of it)
    assert max(p.n_addr for p in entry.programs) < mono.n_addr


def test_partitioned_and_monolithic_cache_separately(rng):
    g = random_graph(rng, 10, 300, 12, locality=40)
    cache = ProgramCache()
    mono = cache.get(g, CompileSpec(n_unit=16))
    part = cache.get(g, CompileSpec(n_unit=16, max_gates=100))
    assert len(mono.programs) == 1 and len(part.programs) >= 2
    assert cache.misses == 2
    assert cache.get(g, CompileSpec(n_unit=16, max_gates=100)) is part


# ---------------------------------------------------------------------------
# sharded serving
# ---------------------------------------------------------------------------

def test_sharded_path_parity_single_device(rng):
    """shard_map path on the host mesh stays exact (1 device here)."""
    g = _graph(rng)
    eng = LogicEngine(CompileSpec(n_unit=16), capacity=64, shard=True)
    assert eng.shard and eng.mesh is not None
    X = rng.integers(0, 2, (45, g.n_inputs)).astype(bool)
    assert (eng.serve(g, X) == g.evaluate(X)).all()


@pytest.mark.slow
def test_sharded_parity_multi_device_subprocess():
    """Data-parallel word-axis serving across 4 forced host devices."""
    code = (
        "import os;"
        "os.environ['XLA_FLAGS']='--xla_force_host_platform_device_count=4';"
        "os.environ['JAX_PLATFORMS']='cpu';"
        "import numpy as np, jax;"
        "from repro.core.gate_ir import random_graph;"
        "from repro.serve import LogicEngine;"
        "assert len(jax.devices()) == 4;"
        "rng = np.random.default_rng(1);"
        "g = random_graph(rng, 10, 200, 8, locality=32);"
        "from repro.core.spec import CompileSpec;"
        "eng = LogicEngine(CompileSpec(n_unit=16), words_per_device=1);"
        "assert eng.shard and eng.capacity == 128;"
        "X = rng.integers(0, 2, (100, 10)).astype(bool);"
        "assert (eng.serve(g, X) == g.evaluate(X)).all();"
        "eng2 = LogicEngine(CompileSpec(n_unit=16, max_gates=80));"
        "assert (eng2.serve(g, X) == g.evaluate(X)).all();"
        "print('sharded-ok')"
    )
    try:
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=300, env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin"})
    except subprocess.TimeoutExpired:
        pytest.skip("multi-device serving smoke exceeded 300s on this host")
    assert "sharded-ok" in out.stdout, out.stderr[-2000:]


# ---------------------------------------------------------------------------
# one wave in flight
# ---------------------------------------------------------------------------

def test_overlap_engages_only_with_work_queued(rng):
    """A lone small request is fetched in the call that launched it; with
    more than a wave queued every wave is fetched by a later call."""
    g = _graph(rng, n_in=8, n_gates=100, n_out=5)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32)
    X = rng.integers(0, 2, (20, 8)).astype(bool)
    assert (eng.serve(g, X) == g.evaluate(X)).all()
    st = eng.stats()
    assert (st["invocations"], st["overlapped_waves"],
            st["overlap_share"]) == (1, 0, 0.0)
    eng.reset_telemetry()
    xs = [rng.integers(0, 2, (30, 8)).astype(bool) for _ in range(4)]
    uids = [eng.submit(g, x) for x in xs]        # 30 + 30 > 32: 4 waves
    eng.drain()
    st = eng.stats()
    assert (st["invocations"], st["overlapped_waves"],
            st["overlap_share"]) == (4, 4, 1.0)
    for uid, x in zip(uids, xs):
        assert (eng.result(uid) == g.evaluate(x)).all()


def test_two_keys_bit_exact_with_a_wave_in_flight(rng):
    """The last wave of one key is in flight while the other key's first
    launches: both stay bit-exact, and keys are served FIFO."""
    ga = _graph(rng, n_in=8, n_gates=90, n_out=5)
    gb = _graph(rng, n_in=11, n_gates=140, n_out=7)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32)
    Xa = rng.integers(0, 2, (70, 8)).astype(bool)     # waves 32, 32, 6
    Xb = rng.integers(0, 2, (50, 11)).astype(bool)    # waves 32, 18
    ua, ub = eng.submit(ga, Xa), eng.submit(gb, Xb)
    done = []
    while not eng.idle:
        done += eng.step()
    assert done == [ua, ub]
    assert (eng.result(ua) == ga.evaluate(Xa)).all()
    assert (eng.result(ub) == gb.evaluate(Xb)).all()
    assert eng.stats()["overlapped_waves"] == eng.invocations == 5


def test_oversized_request_chunks_over_overlapped_waves(rng):
    """A request of five waves: the first call only launches, each later
    call fetches the wave before, and the request completes with the
    fetch of its last chunk."""
    g = _graph(rng, n_in=8, n_gates=100, n_out=5)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32)
    X = rng.integers(0, 2, (150, 8)).astype(bool)
    uid = eng.submit(g, X)
    calls = []
    while not eng.idle:
        calls.append(eng.step())
    assert calls == [[], [], [], [], [], [uid]]
    assert (eng.result(uid) == g.evaluate(X)).all()
    assert eng.stats()["overlapped_waves"] == eng.invocations == 5


def test_wave_in_flight_keeps_engine_busy_and_its_rows(rng):
    """``idle`` is false while a wave is in flight with nothing queued;
    its rows stay held until it is scattered, and after ``drain`` every
    row of both tables is free."""
    g = _graph(rng, n_in=8, n_gates=100, n_out=5)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32)
    u1 = eng.submit(g, rng.integers(0, 2, (30, 8)).astype(bool))
    u2 = eng.submit(g, rng.integers(0, 2, (25, 8)).astype(bool))
    assert eng.step() == []                  # wave 1 launched, kept
    assert eng.step() == [u1]                # wave 2 launched, 1 fetched
    assert not eng.idle
    assert [t.n_active for t in eng.slot_tables] == [0, 25]
    with pytest.raises(RuntimeError, match="still in flight"):
        eng.result(u2)
    eng.drain()
    assert eng.idle and eng.result(u2).shape == (25, 5)
    assert all(t.n_free == eng.capacity for t in eng.slot_tables)
    assert eng.stats()["slot_high_water"] == 30


def test_eviction_of_a_key_with_its_wave_in_flight(rng):
    """The LRU may evict a key whose wave is in flight: the wave keeps
    its own result and chunks, and the key's queued rest recompiles."""
    g1 = _graph(rng, n_in=8, n_gates=80, n_out=5)
    g2 = _graph(rng, n_in=8, n_gates=90, n_out=5)
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32, max_programs=1)
    X1 = rng.integers(0, 2, (70, 8)).astype(bool)     # waves 32, 32, 6
    X2 = rng.integers(0, 2, (10, 8)).astype(bool)
    u1, u2 = eng.submit(g1, X1), eng.submit(g2, X2)
    assert eng.step() == []                  # g1's first wave in flight
    eng.cache.evict()
    assert len(eng.cache) == 0
    eng.drain()
    assert (eng.result(u1) == g1.evaluate(X1)).all()
    assert (eng.result(u2) == g2.evaluate(X2)).all()
    assert eng.stats()["overlapped_waves"] == eng.invocations == 4


class _LostResult:
    """A runner's result whose fetch fails, as a device lost mid-wave."""

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("device lost")


@pytest.mark.parametrize("fails_at", ["launch", "fetch"])
def test_failed_wave_fails_only_its_own_requests(rng, fails_at):
    """Three keys, a wave each: B's wave raises at its launch (A in
    flight) or at its fetch (C launched, in flight).  B's request fails
    with the error, the wave beside it stays in flight, and A and C
    complete bit-exact with every row freed."""
    graphs = [_graph(rng, n_in=8, n_gates=80 + 10 * i, n_out=5)
              for i in range(3)]
    eng = LogicEngine(CompileSpec(n_unit=8), capacity=32)
    xs = [rng.integers(0, 2, (20, 8)).astype(bool) for _ in graphs]
    ua, ub, uc = (eng.submit(g, x) for g, x in zip(graphs, xs))
    runners = eng._entry(graphs[1]).runners

    def broken(bits):
        if fails_at == "launch":
            raise RuntimeError("kernel failed to compile")
        return _LostResult()
    runners[eng._exec_key] = broken
    assert eng.step() == []                  # A launched and kept
    if fails_at == "launch":
        with pytest.raises(RuntimeError, match="failed to compile"):
            eng.step()                       # B's launch raises
    else:
        assert eng.step() == [ua]            # B launched, A fetched
        with pytest.raises(RuntimeError, match="device lost"):
            eng.step()                       # C launched, B's fetch raises
    assert not eng.idle                      # A or C still in flight
    eng.drain()
    with pytest.raises(RuntimeError, match="failed in its wave") as ei:
        eng.result(ub)
    assert isinstance(ei.value.__cause__, RuntimeError)
    assert (eng.result(ua) == graphs[0].evaluate(xs[0])).all()
    assert (eng.result(uc) == graphs[2].evaluate(xs[2])).all()
    assert all(t.n_free == eng.capacity for t in eng.slot_tables)
    assert eng.invocations == 2
