"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. Usage:
  PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]

``--json`` additionally writes the rows as ``{name: {us, derived}}`` —
the machine-readable perf trajectory (``BENCH_logic.json``) that future
PRs diff against.  Every row that compiles a logic program also records
the serialized :class:`~repro.core.spec.CompileSpec` it compiled
against (``"spec"`` key), so the perf trajectory is attributable to an
exact compilation target.  The JSON also carries a ``bench_env`` header
block (host hash, cpu count, jax/jaxlib versions, interpret flag,
timestamp) so wall-clock rows are attributable to the machine that
produced them — schema in benchmarks/README.md.
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np

from benchmarks import baselines, workloads
from repro.core.cost_model import CostModel, FfclStats, FpgaFabric, TpuFabric
from repro.core.gate_ir import random_graph
from repro.core.optimizer import binary_search, sweep
from repro.core.scheduler import compile_graph
from repro.core.simulator import simulate_no_pipeline, simulate_pipeline
from repro.core.spec import CompileSpec

ROWS: list[tuple[str, float, str, dict | None]] = []
CLOCK = TpuFabric().clock_hz


def row(name: str, us: float, derived: str = "",
        spec: CompileSpec | None = None) -> None:
    ROWS.append((name, us, derived,
                 None if spec is None else spec.to_dict()))
    print(f"{name},{us:.3f},{derived}")


def cycles_us(cycles: float) -> float:
    return cycles / CLOCK * 1e6


def timed(fn, reps: int, *, warmup: int = 1) -> float:
    """Mean seconds per call of ``fn`` over ``reps`` calls.

    The shared wall-clock discipline for every measured loop in this
    harness: ``warmup`` unwarmed calls run first (jit trace/compile and
    first-touch allocation excluded from the measurement), and every
    call — warmup included — is synchronized through
    ``jax.block_until_ready`` on its result, so jax's asynchronous
    dispatch can never under-report a row (numpy results pass through
    unchanged)."""
    import jax
    for _ in range(max(0, warmup)):
        jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn())
    return (time.perf_counter() - t0) / reps


def bench_env() -> dict:
    """The ``bench_env`` header block: enough provenance to attribute a
    wall-clock row to the machine/backends that produced it, without
    leaking the hostname itself (hashed)."""
    import hashlib
    import os
    import socket

    import jax
    import jaxlib

    from repro.kernels.platform import resolve_interpret
    return {
        "host": hashlib.blake2b(socket.gethostname().encode(),
                                digest_size=4).hexdigest(),
        "cpu_count": os.cpu_count(),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        # what the kernels resolved to on this backend
        "interpret": resolve_interpret(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
    }


# ---------------------------------------------------------------------------
# Fig. 6: cost model vs "actual" (discrete-event simulator), layer conv7/8
# ---------------------------------------------------------------------------

def bench_cost_model_validation(quick: bool) -> None:
    wl = workloads.build_workload(
        [workloads.VGG16_LAYERS[6]], n_samples=96 if quick else 160)
    lw = wl[0]
    model = CostModel()
    m = 16 if quick else 64     # filters pipelined per launch
    errs = []
    for n_unit in (64, 256, 1024):
        # the workload graphs are pre-optimized (workloads.py), so the
        # compile target itself runs no pass pipeline
        spec = CompileSpec(n_unit=n_unit, optimize="none")
        prog = compile_graph(lw.graph, spec)
        sim = simulate_pipeline([prog] * m, n_input_vectors=lw.n_patches)
        # stats from the compiled program: with step fusion enabled the
        # model must charge the scheduled step count, not eq. 23's
        mdl = model.total_cycles(FfclStats.from_program(prog), n_unit,
                                 lw.n_patches, m_modules=m)
        err = (mdl - sim.total_cycles) / sim.total_cycles
        errs.append(abs(err))
        row(f"fig6.model_vs_sim.n{n_unit}", cycles_us(sim.total_cycles),
            f"model_err={err:+.1%}", spec=spec)
    row("fig6.max_abs_err", 0.0, f"{max(errs):.1%} (paper: <10%)")


# ---------------------------------------------------------------------------
# Fig. 7: latency split (data movement vs compute) across n_unit
# ---------------------------------------------------------------------------

def bench_latency_split(quick: bool) -> None:
    wl = workloads.build_workload(
        [workloads.VGG16_LAYERS[6]], n_samples=96 if quick else 160)
    lw = wl[0]
    model = CostModel()
    for n_unit in (16, 64, 256, 1024, 4096):
        b = model.breakdown(lw.stats, n_unit, lw.n_patches)
        share = b.n_data_moves / (b.n_data_moves + b.n_compute)
        row(f"fig7.split.n{n_unit}", cycles_us(b.n_total_pipelined),
            f"dm_share={share:.0%} bound={b.bound}")


# ---------------------------------------------------------------------------
# Fig. 6 / §8.1: U-shaped design space + binary search
# ---------------------------------------------------------------------------

def bench_pareto_search(quick: bool) -> None:
    wl = workloads.build_workload(
        workloads.VGG16_LAYERS[:4] if quick else workloads.VGG16_LAYERS,
        n_samples=96 if quick else 160)
    layers = workloads.cost_model_layers(wl)
    model = CostModel()
    grid = [2 ** k for k in range(2, 13)]
    swp = sweep(model, layers, grid)
    res = binary_search(model, layers, n_unit_max=4096)
    row("pareto.sweep_best", cycles_us(swp.best_cycles),
        f"n_unit={swp.best_n_unit}")
    row("pareto.binary_search", cycles_us(res.best_cycles),
        f"n_unit={res.best_n_unit} probes={len(res.evaluations)}")


# ---------------------------------------------------------------------------
# Figs. 9/10: MAC vs XNOR vs NullaDSP, VGG16 + LeNet-5
# ---------------------------------------------------------------------------

def bench_nn_e2e(quick: bool) -> None:
    """Figs. 9/10 on BOTH fabrics.

    fpga (paper-faithful constants): reproduces the paper's headline —
    NullaDSP at its Pareto-optimal unit count beats the (DDR-bound) MAC
    array at 1024 units (paper VGG16: 2.99 ms vs 5.72 ms ~ 1.9x); XNOR is
    fastest but least accurate.

    tpu (hardware adaptation): on an HBM-class memory system the MAC
    baseline is compute-bound and far stronger — the FFCL win shrinks.
    Recorded as a finding in DESIGN.md §2 / EXPERIMENTS.md §Perf.
    """
    for fab_name, fabric in (("fpga", FpgaFabric()), ("tpu", TpuFabric())):
        model = CostModel(fabric)
        for net, layer_spec in (("vgg16", workloads.VGG16_LAYERS),
                                ("lenet5", workloads.LENET5_LAYERS)):
            spec = layer_spec[:4] if (quick and net == "vgg16") else \
                layer_spec
            wl = workloads.build_workload(spec,
                                          n_samples=128 if quick else 400)
            cls = workloads.cost_model_layers(wl)
            us = 1e6 / fabric.clock_hz
            units = (140, 512) if net == "lenet5" else (1024, 4096)
            for n_unit in units:
                mac = baselines.mac_cycles(spec, n_unit, fabric)
                xnor = baselines.xnor_cycles(spec, n_unit, fabric)
                nd = baselines.nulladsp_cycles(cls, n_unit, model)
                row(f"fig9_10.{fab_name}.{net}.n{n_unit}.mac", mac * us, "")
                row(f"fig9_10.{fab_name}.{net}.n{n_unit}.xnor", xnor * us, "")
                row(f"fig9_10.{fab_name}.{net}.n{n_unit}.nulladsp", nd * us,
                    f"vs_mac={mac / nd:.2f}x")
            best = binary_search(model, cls, n_unit_max=4096)
            mac1024 = baselines.mac_cycles(spec, 1024, fabric)
            row(f"fig9_10.{fab_name}.{net}.pareto.nulladsp",
                best.best_cycles * us,
                f"n_unit={best.best_n_unit} "
                f"vs_mac1024={mac1024 / best.best_cycles:.2f}x")
            # eq. 25: k parallel compute kernels share the SAME unit budget
            # as the MAC baseline — the paper's headline configuration
            par_c, n_per, k = baselines.nulladsp_parallel_best(
                cls, 1024, model)
            row(f"fig9_10.{fab_name}.{net}.eq25.nulladsp", par_c * us,
                f"{k}x{n_per}u vs_mac1024={mac1024 / par_c:.2f}x"
                + (" (paper: ~1.9x vgg16)" if fab_name == "fpga" else ""))


# ---------------------------------------------------------------------------
# Table 4: resource utilization -> VMEM/HBM working sets per design size
# ---------------------------------------------------------------------------

def bench_resources(quick: bool) -> None:
    wl = workloads.build_workload(
        [workloads.VGG16_LAYERS[6]], n_samples=96 if quick else 160)
    lw = wl[0]
    w_words = -(-lw.n_patches // 32)
    for label, n_unit in (("large", 1000), ("medium", 250), ("small", 180),
                          ("tiny", 100)):
        spec = CompileSpec(n_unit=n_unit, alloc="liveness", optimize="none")
        prog = compile_graph(lw.graph, spec)
        data_buf = prog.n_addr * w_words * 4
        streams = prog.n_steps * prog.n_unit * (3 * 4 + 1)
        row(f"table4.{label}.n{n_unit}", 0.0,
            f"vmem_data={data_buf / 2 ** 10:.0f}KiB "
            f"streams={streams / 2 ** 10:.0f}KiB steps={prog.n_steps}",
            spec=spec)


# ---------------------------------------------------------------------------
# kernel micro-benchmarks (wall-clock; interpret mode on CPU)
# ---------------------------------------------------------------------------

def bench_kernels(quick: bool) -> None:
    import jax.numpy as jnp

    from repro.kernels.logic_dsp import logic_infer_bits
    from repro.kernels.xnor_gemm import xnor_gemm

    rng = np.random.default_rng(0)
    g = random_graph(rng, 32, 1500, 16, locality=128)
    # optimize="none" keeps the kernel row comparable across snapshots
    # (the measured program is exactly the 1500-gate random netlist)
    spec = CompileSpec(n_unit=64, alloc="liveness", optimize="none")
    prog = compile_graph(g, spec)
    X = rng.integers(0, 2, (4096, 32)).astype(bool)
    reps = 2 if quick else 5
    dt = timed(lambda: logic_infer_bits(prog, X), reps)
    row("kernel.logic_dsp.interp", dt * 1e6,
        f"gates={prog.n_gates} steps={prog.n_steps} batch=4096 "
        f"homog={prog.homogeneous.mean():.0%}", spec=spec)

    a = jnp.asarray(rng.integers(0, 2, (256, 2304)), jnp.uint8)
    b = jnp.asarray(rng.integers(0, 2, (256, 2304)), jnp.uint8)
    dt = timed(lambda: xnor_gemm(a, b), reps)
    row("kernel.xnor_gemm.interp", dt * 1e6, "m=n=256 k=2304")


# ---------------------------------------------------------------------------
# serving throughput: LogicEngine batched vs single-shot (serve/logic_engine)
# ---------------------------------------------------------------------------

def bench_serve_logic(quick: bool) -> None:
    from repro.serve import LogicEngine

    rng = np.random.default_rng(3)
    g = random_graph(rng, 32, 1200 if quick else 2000, 16, locality=128)
    sizes = ([48, 17, 96, 33, 62] if quick else
             [48, 17, 96, 33, 62, 130, 5, 81, 256, 44])
    reqs = [rng.integers(0, 2, (n, 32)).astype(bool) for n in sizes]
    total = sum(sizes)
    # host-side wave overhead is ~ms-scale: more reps than the kernel
    # benches to keep the serving rows stable on small containers
    reps = 5 if quick else 10

    # batched: slot-packed requests share fabric invocations
    spec = CompileSpec(n_unit=64)
    eng = LogicEngine(spec, capacity=256)

    def wave(engine):
        uids = [engine.submit(g, bits) for bits in reqs]
        engine.drain()
        return [engine.result(uid) for uid in uids]

    wave(eng)                                  # compile + jit warmup
    eng.reset_telemetry()       # occupancy of the timed waves only
    dt = timed(lambda: wave(eng), reps, warmup=0)
    st = eng.stats()
    row("serve.logic_dsp.batched", dt * 1e6,
        f"samples_per_s={total / dt:.0f} reqs={len(sizes)} "
        f"occ={st['mean_occupancy']:.0%}", spec=spec)

    # single-shot baseline: one fabric invocation per request (per-shape
    # jits warmed; same optimized netlist as the engine serves, so the
    # gap left is the engine's batching amortization)
    from repro.kernels.logic_dsp import logic_infer_bits
    prog = compile_graph(g, spec)
    dt_single = timed(
        lambda: [logic_infer_bits(prog, bits) for bits in reqs], reps)
    row("serve.logic_dsp.single_shot", dt_single * 1e6,
        f"samples_per_s={total / dt_single:.0f} "
        f"vs_batched={dt_single / dt:.2f}x", spec=spec)

    # program-cache effect: structurally equal resubmission vs cold compile
    fresh = LogicEngine(spec, capacity=256)
    probe = reqs[0]
    t0 = time.perf_counter()
    fresh.serve(g, probe)                              # compile + trace
    cold = time.perf_counter() - t0
    g2 = g.copy()
    g2.name = "resubmitted"
    t0 = time.perf_counter()
    fresh.serve(g2, probe)                             # registry hit
    warm = time.perf_counter() - t0
    row("serve.logic_dsp.program_cache", warm * 1e6,
        f"cold_us={cold * 1e6:.0f} speedup={cold / max(warm, 1e-9):.0f}x "
        f"hits={fresh.cache.hits} misses={fresh.cache.misses}", spec=spec)

    # partitioned pipeline serving (multi-FFCL task pipelining)
    pspec = spec.with_(max_gates=400 if quick else 700)
    peng = LogicEngine(pspec, capacity=256)
    wave(peng)
    peng.reset_telemetry()
    dt_part = timed(lambda: wave(peng), reps, warmup=0)
    n_parts = len(peng.cache.get(g, peng.spec).programs)
    row("serve.logic_dsp.partitioned", dt_part * 1e6,
        f"programs={n_parts} samples_per_s={total / dt_part:.0f} "
        f"vs_mono={dt_part / dt:.2f}x", spec=pspec)


# ---------------------------------------------------------------------------
# fleet warm start: cold compile vs artifact-store load vs in-memory hit
# ---------------------------------------------------------------------------

def bench_warm_start(quick: bool) -> None:
    """``serve.warm_start.*`` rows: what the artifact store buys a fresh
    serving process.  Three ``ProgramCache.get`` latencies for the SAME
    (graph, spec): a cold cache with no store (full compile), a cold
    cache over a populated store (verified load), and a warm in-memory
    repeat (registry hit).  Counter-pinned — the store row asserts zero
    compiles — so a silent fallback-to-compile can never masquerade as
    a fast load.  Schema in benchmarks/README.md."""
    import tempfile

    from repro.core.artifact_store import ArtifactStore
    from repro.serve import ProgramCache

    rng = np.random.default_rng(9)
    g = random_graph(rng, 32, 1200 if quick else 3000, 16, locality=128)
    spec = CompileSpec(n_unit=64)
    reps = 3 if quick else 5

    def timed_get(cache):
        t0 = time.perf_counter()
        cache.get(g, spec)
        return time.perf_counter() - t0

    cold = min(timed_get(ProgramCache()) for _ in range(reps))

    with tempfile.TemporaryDirectory(prefix="bench-warm-") as root:
        ProgramCache(store=ArtifactStore(root)).get(g, spec)   # publish
        loads, warm_cache = [], None
        for _ in range(reps):
            warm_cache = ProgramCache(store=ArtifactStore(root))
            loads.append(timed_get(warm_cache))
        load = min(loads)
        st = warm_cache.stats()
        assert st["compiles"] == 0 and st["store_hits"] == 1, st
        hit = min(timed_get(warm_cache) for _ in range(reps))

    row("serve.warm_start.cold_compile", cold * 1e6,
        f"gates={g.n_gates}", spec=spec)
    row("serve.warm_start.store_load", load * 1e6,
        f"vs_cold={cold / max(load, 1e-9):.1f}x compiles=0 store_hits=1",
        spec=spec)
    row("serve.warm_start.memory_hit", hit * 1e6,
        f"vs_cold={cold / max(hit, 1e-9):.0f}x", spec=spec)


# ---------------------------------------------------------------------------
# static schedule verifier: proof overhead vs the compile it certifies
# ---------------------------------------------------------------------------

def bench_verify(quick: bool) -> None:
    """``verify.overhead.*`` / ``verify.load.*`` rows (DESIGN.md §13):
    what the static schedule verifier costs, gated in-bench.

      * ``verify.overhead.<case>``: added wall-clock of compiling with
        ``verify="compile"`` over the same compile with the verifier
        off — the price of turning the knob on.  Asserted ``<= 25%`` of
        the unverified compile for both the monolithic and the
        partitioned case (the partitioned proof reuses the clusters the
        compiler just derived, so it does not re-pay partitioning);
      * ``verify.load.<case>``: standalone ``verify_artifact`` on the
        finished artifact — the store-load / CLI audit path.  For
        partitioned artifacts this INCLUDES the deterministic partition
        re-derivation (the load path's trust anchor), so it is
        reported, not gated against the compile.

    Every timed proof is also asserted clean (zero diagnostics).
    Schema in benchmarks/README.md."""
    from repro.core.compiler import LogicCompiler
    from repro.core.verify import verify_artifact

    rng = np.random.default_rng(13)
    g = random_graph(rng, 24, 1500 if quick else 4000, 12, locality=96)
    reps = 3 if quick else 5
    comp = LogicCompiler()
    cases = [("mono", CompileSpec(n_unit=64)),
             ("partitioned", CompileSpec(
                 n_unit=64, max_gates=400 if quick else 1000))]
    def once(fn):
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    for label, spec in cases:
        # interleaved off/on pairs with one unmeasured warmup pair, min
        # per side: common-mode host noise (the surrounding harness is
        # busy) cancels instead of landing entirely on one variant
        comp.compile(g, spec)
        comp.compile(g, spec.with_(verify="compile"))
        off, on = [], []
        for _ in range(reps):
            off.append(once(lambda: comp.compile(g, spec)))
            on.append(once(lambda: comp.compile(
                g, spec.with_(verify="compile"))))
        off, on = min(off), min(on)
        overhead = max(on - off, 0.0)
        ratio = overhead / max(off, 1e-9)
        assert ratio <= 0.25, \
            f"{label}: verify overhead {ratio:.1%} exceeds the 25% gate"
        art = comp.compile(g, spec)
        t_load, report = None, None
        for _ in range(reps):
            t0 = time.perf_counter()
            report = verify_artifact(art)
            dt = time.perf_counter() - t0
            t_load = dt if t_load is None else min(t_load, dt)
        assert report.ok, report.summary()
        row(f"verify.overhead.{label}", overhead * 1e6,
            f"ratio={ratio:.3f} compile_us={off * 1e6:.0f} "
            f"programs={len(art.programs)} diagnostics=0 gate<=0.25",
            spec=spec)
        row(f"verify.load.{label}", t_load * 1e6,
            f"steps={report.checked['steps']} "
            f"terms={report.checked['terms']} diagnostics=0", spec=spec)


# ---------------------------------------------------------------------------
# wall-clock calibration: phase fit quality + objective="wallclock" DSE
# ---------------------------------------------------------------------------

def bench_calibration(quick: bool) -> None:
    """``calib.*`` / ``dse.wallclock.*`` rows (DESIGN.md §12): fit the
    per-phase wall-clock model on the seeded probe grid and gate it
    in-bench —

      * ``calib.fit.<phase>``: fitted coefficients/offset per phase;
      * ``calib.err.<phase>``: median |pred-measured|/measured of the
        fit, ASSERTED <= 25% per phase;
      * ``dse.wallclock.<workload>``: the n_unit the calibrated
        ``objective="wallclock"`` auto-search picks, with its MEASURED
        fused-path latency vs the measured best over the exhaustive
        probe-unit sweep — ASSERTED within 10%.

    Gates live here (not only in tests) so a perf snapshot that shipped
    with a drifted calibration is impossible: the harness itself fails.
    """
    from repro.core import calibrate
    from repro.core.compiler import LogicCompiler
    from repro.core.cost_model import n_subkernels

    reps = 5 if quick else 7
    graphs = calibrate.default_probe_graphs(quick=quick)
    units = calibrate.default_probe_units(quick=quick)
    probes = calibrate.collect_probes(graphs, units, reps=reps)
    cal = calibrate.fit_calibration(probes, meta={
        "grid": "quick" if quick else "full", "reps": reps})

    for phase in calibrate.PHASES:
        f = cal.fits[phase]
        coefs = " ".join(f"{c:.3e}" for c in f.coefs)
        row(f"calib.fit.{phase}", f.offset * 1e6,
            f"coefs=[{coefs}] probes={f.n_probes}")
        err = f.median_abs_rel_err
        assert err <= 0.25, \
            f"calibration phase {phase!r} median error {err:.1%} > 25%"
        row(f"calib.err.{phase}", 0.0, f"median_abs_rel_err={err:.1%}")

    # the DSE gate: per calibration workload, the wallclock-objective
    # auto pick's MEASURED latency must be within 10% of the measured
    # best over the exhaustive probe-unit sweep (the same grid the fit
    # saw; the compiler is clamped to its range so the search and the
    # sweep explore the same design space).  Two measurement passes,
    # both round-robin interleaved (sequential per-candidate loops let
    # host drift swamp the ~10% differences this gate resolves):
    # first the sweep locates the apparently-best candidate, then the
    # pick and that candidate are RE-measured head to head — a min over
    # many noisy candidates is biased low (extreme-value selection), so
    # gating against the sweep's raw min would fail even a perfect pick
    # on a flat design space.
    from repro.kernels.logic_dsp.ops import phased_infer_bits
    compiler = LogicCompiler(calibration=cal, n_unit_min=min(units),
                             n_unit_max=max(units))
    rng = np.random.default_rng(0)

    def roundrobin(progs, bits, n_rounds):
        best = {u: float("inf") for u in progs}
        for p in progs.values():                          # warm traces
            phased_infer_bits(p, bits)
        for _ in range(n_rounds):
            for u, p in progs.items():
                _, phases = phased_infer_bits(p, bits)
                best[u] = min(best[u], sum(phases.values()))
        return best

    def duel(p_pick, p_best, bits, n_rounds):
        """Median of per-round PAIRED pick/best latency ratios (plus
        the pick's median seconds).  Pairing inside each round cancels
        the sustained host-load shifts that an unpaired min-over-rounds
        comparison is still exposed to."""
        ratios, t_picks = [], []
        for _ in range(n_rounds):
            _, ph_a = phased_infer_bits(p_pick, bits)
            _, ph_b = phased_infer_bits(p_best, bits)
            t_picks.append(sum(ph_a.values()))
            ratios.append(t_picks[-1] / sum(ph_b.values()))
        return float(np.median(ratios)), float(np.median(t_picks))

    for label, g in graphs.items():
        spec, search = compiler.resolve(
            g, CompileSpec(n_unit="auto", objective="wallclock",
                           optimize="none"))
        pick = spec.n_unit
        progs = {u: compile_graph(g, CompileSpec(n_unit=u,
                                                 optimize="none"))
                 for u in sorted(set(units) | {pick})}
        bits = rng.integers(0, 2, (1024, g.n_inputs)).astype(bool)
        sweep = roundrobin(progs, bits, reps)
        sweep_best = min(sweep, key=sweep.get)
        if pick == sweep_best:
            ratio, t_pick = 1.0, sweep[pick]
        else:
            ratio, t_pick = duel(progs[pick], progs[sweep_best], bits,
                                 3 * reps)
        stats = FfclStats.from_graph(g)
        row(f"dse.wallclock.{label}", t_pick * 1e6,
            f"n_unit={pick} vs_sweep_best={ratio:.3f}x "
            f"sweep_best_n={sweep_best} "
            f"cycles_pick={search.alt.best_n_unit} "
            f"nsk={n_subkernels(stats, pick)}", spec=spec)
        assert ratio <= 1.10, \
            (f"wallclock pick n_unit={pick} measured {ratio:.2f}x the "
             f"sweep best (n_unit={sweep_best}) on {label} (> 1.10x)")


# ---------------------------------------------------------------------------
# serving front door under load: admission, deadlines, shedding (serve/)
# ---------------------------------------------------------------------------

def bench_serve_traffic(quick: bool) -> None:
    """``serve.traffic.*`` rows: the front door driven closed-loop by a
    two-tenant Poisson + heavy-tail (Pareto) trace.  ``us`` on the
    latency rows is the percentile itself; shed/deadline-miss rows are
    ``derived``-only rates.  Schema in benchmarks/README.md."""
    import asyncio

    from repro.serve import (FrontDoor, Priority, TrafficPattern,
                             build_trace, run_trace)

    rng = np.random.default_rng(5)
    g_a = random_graph(rng, 16, 300 if quick else 800, 10, locality=64)
    g_b = random_graph(rng, 12, 200 if quick else 500, 8, locality=64)
    spec = CompileSpec(n_unit=32)
    n = 60 if quick else 200
    trace = build_trace([
        TrafficPattern(tenant="vision", rate_rps=150.0, n_requests=n,
                       size_mean=40, deadline_s=0.5,
                       priority_mix=((Priority.HIGH, 0.2),
                                     (Priority.NORMAL, 0.8))),
        TrafficPattern(tenant="ranking", rate_rps=100.0, n_requests=n,
                       arrival="pareto", pareto_alpha=1.4,
                       size_mean=24, deadline_s=0.5,
                       priority_mix=((Priority.NORMAL, 0.5),
                                     (Priority.BATCH, 0.5))),
    ], seed=11)

    async def drive():
        door = FrontDoor(spec=spec, capacity=128, max_queue=24,
                         default_deadline_s=0.5)
        door.register("vision", g_a, max_inflight=8)
        door.register("ranking", g_b, max_inflight=8)
        async with door:
            # warm compile/jit caches and the admission controller's
            # wave-time window so the trace measures serving, not cold
            # starts
            for _ in range(5):
                for name, g in (("vision", g_a), ("ranking", g_b)):
                    bits = rng.integers(0, 2, (48, g.n_inputs)).astype(bool)
                    await door.submit(name, bits, deadline_s=30.0)
            door.reset_metrics()
            report = await run_trace(door, trace, seed=13)
        return report, door.metrics()

    report, m = asyncio.run(drive())
    sheds = " ".join(f"{k}={v}" for k, v in
                     sorted(report.shed_by_code.items()))
    row("serve.traffic.p50", report.p50_ms * 1e3 if report.p50_ms else 0.0,
        f"completed={report.completed} offered={report.offered}", spec=spec)
    row("serve.traffic.p99", report.p99_ms * 1e3 if report.p99_ms else 0.0,
        f"wave_est_ms={m['wave_est_ms']:.2f}", spec=spec)
    row("serve.traffic.goodput", 0.0,
        f"samples_per_s={report.goodput_sps:.0f} "
        f"elapsed_s={report.elapsed_s:.2f}", spec=spec)
    row("serve.traffic.shed_rate", 0.0,
        f"rate={report.shed_rate:.4f} shed={report.shed}"
        + (f" {sheds}" if sheds else ""), spec=spec)
    row("serve.traffic.deadline_miss", 0.0,
        f"rate={report.deadline_miss_rate:.4f} "
        f"missed={report.deadline_missed} retries={m['retries']}", spec=spec)


# ---------------------------------------------------------------------------
# end-to-end NullaNet classifier flow (flow/): train -> FFCL -> serve -> acc
# ---------------------------------------------------------------------------

def bench_flow_e2e(quick: bool) -> None:
    from repro.flow import FlowConfig, input_bits, run_flow
    from repro.serve import LogicEngine

    cfg = FlowConfig(n_features=10 if quick else 12,
                     hidden=(8, 6) if quick else (10, 8),
                     n_classes=4, n_samples=1200 if quick else 4000,
                     train_steps=120 if quick else 300,
                     spec=CompileSpec(n_unit=32))
    report, clf = run_flow(cfg)
    row("flow.e2e.convert", report.convert_s * 1e6,
        f"layers={len(report.layers)} gates={report.n_gates} "
        f"steps={report.n_steps}", spec=cfg.spec)
    row("flow.e2e.parity", 0.0,
        f"parity={'EXACT' if report.parity else 'approx'} "
        f"bit_identical={report.bit_identical} "
        f"logic_acc={report.logic_acc['pallas']:.4f} "
        f"binarized_acc={report.binarized_acc:.4f} "
        f"float_acc={report.float_acc:.4f}")
    row("flow.e2e.sim_cycles", cycles_us(report.sim_cycles),
        f"bound={report.sim_bound} n_vectors={report.n_val}")

    # warm per-backend inference wall-clock over the same val set the
    # reported accuracies used
    _, _, xv, _ = cfg.load_data()
    bits = input_bits(xv)
    engine = LogicEngine(cfg.spec, capacity=256)
    reps = 3 if quick else 5

    # single-launch pin (counter hook, not timing): a FRESH chain
    # megaprogram — its runner cache is empty, so this traces once — must
    # execute the whole hidden stack in exactly ONE pallas_call, and the
    # result must be bit-exact against the reference backend
    from repro.core.scheduler import build_megaprogram
    from repro.kernels.logic_dsp import kernel as _kern
    from repro.kernels.logic_dsp.ops import mega_infer_bits
    fresh_mega = build_megaprogram(clf.programs, mode="chain")
    before = _kern.launch_count()
    h_mega = mega_infer_bits(fresh_mega, bits)
    launches = _kern.launch_count() - before
    assert launches == 1, \
        f"megakernel took {launches} pallas_call launches, expected 1"
    h_ref = clf.hidden_bits(bits, backend="reference")
    assert (h_mega == h_ref).all(), "megakernel diverged from reference"

    for backend in ("reference", "pallas", "megakernel", "engine"):
        dt = timed(lambda b=backend: clf.hidden_bits(bits, backend=b,
                                                     engine=engine), reps)
        extra = " launches=1 parity=exact" if backend == "megakernel" else ""
        row(f"flow.e2e.{backend}", dt * 1e6,
            f"samples_per_s={len(bits) / dt:.0f} batch={len(bits)}{extra}",
            spec=cfg.spec)


# ---------------------------------------------------------------------------
# compiler wall-clock: vectorized stream emission (scheduler.compile_graph)
# ---------------------------------------------------------------------------

def bench_compile(quick: bool) -> None:
    # default ISF density (400): the same conv7 FFCL the full nn_e2e
    # benchmarks compile, a few hundred gates
    wl = workloads.build_workload([workloads.VGG16_LAYERS[6]])
    g = wl[0].graph
    reps = 20 if quick else 50
    # optimize="none": these rows time the SCHEDULER (levelize -> sort ->
    # fuse -> alloc -> emit), not the pass pipeline (opt.* rows time that)
    for alloc in ("direct", "liveness"):
        spec = CompileSpec(n_unit=256, alloc=alloc, optimize="none")
        compile_graph(g, spec)                             # warm caches
        t0 = time.perf_counter()
        for _ in range(reps):
            prog = compile_graph(g, spec)
        row(f"compile.vgg16_conv7.{alloc}",
            (time.perf_counter() - t0) / reps * 1e6,
            f"gates={g.n_gates} steps={prog.n_steps}", spec=spec)
    # VGG16-scale stress: tens of thousands of gates through the same path
    rng = np.random.default_rng(7)
    n_gates = 10_000 if quick else 30_000
    big = random_graph(rng, 64, n_gates, 32, locality=256)
    for alloc in ("direct", "liveness"):
        spec = CompileSpec(n_unit=256, alloc=alloc, optimize="none")
        t0 = time.perf_counter()
        prog = compile_graph(big, spec)
        row(f"compile.random{n_gates // 1000}k.{alloc}",
            (time.perf_counter() - t0) * 1e6,
            f"gates={big.n_gates} steps={prog.n_steps}", spec=spec)


# ---------------------------------------------------------------------------
# gate-level optimization pipeline (core/opt.py): gate/step/compile deltas
# ---------------------------------------------------------------------------

def bench_opt(quick: bool) -> None:
    """``opt.*`` rows: what the default pass pipeline buys versus raw
    synthesis on (a) the e2e NullaNet workload and (b) a random-graph
    stress case — gate count, scheduled steps, and compile wall-clock.
    ``us`` is the pass-pipeline wall-clock itself (the price paid once
    per distinct structure; the serving registry memoizes it)."""
    from repro.core.nullanet import (BinaryMLPConfig, train_binary_mlp)
    from repro.core.opt import PassManager
    from repro.flow import FlowConfig, hard_forward, input_bits
    from repro.flow.convert import layer_graph

    def ab_rows(tag: str, raw_graphs: list, n_unit: int) -> None:
        pm = PassManager.default()
        spec = CompileSpec(n_unit=n_unit, alloc="liveness", optimize="none")
        t0 = time.perf_counter()
        opt_graphs = [pm.run(g).graph for g in raw_graphs]
        opt_us = (time.perf_counter() - t0) * 1e6
        g_raw = sum(g.n_gates for g in raw_graphs)
        g_opt = sum(g.n_gates for g in opt_graphs)
        row(f"opt.{tag}.gates", opt_us,
            f"raw={g_raw} opt={g_opt} ({(g_opt - g_raw) / g_raw:+.0%})")
        t0 = time.perf_counter()
        s_raw = sum(compile_graph(g, spec).n_steps for g in raw_graphs)
        raw_c = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        s_opt = sum(compile_graph(g, spec).n_steps for g in opt_graphs)
        opt_c = (time.perf_counter() - t0) * 1e6
        row(f"opt.{tag}.steps", opt_c,
            f"raw={s_raw} opt={s_opt} ({(s_opt - s_raw) / s_raw:+.0%}) "
            f"raw_compile_us={raw_c:.0f}", spec=spec)

    # (a) the e2e NullaNet classifier workload (same config family as
    # flow.e2e.*): every hidden layer, raw espresso factoring vs pipeline
    cfg = FlowConfig(n_features=10 if quick else 12,
                     hidden=(8, 6) if quick else (10, 8),
                     n_classes=4, n_samples=1200 if quick else 4000,
                     train_steps=120 if quick else 300,
                     spec=CompileSpec(n_unit=32))
    xt, yt, _, _ = cfg.load_data()
    mcfg = BinaryMLPConfig(n_features=cfg.n_features, hidden=cfg.hidden,
                           n_classes=cfg.n_classes, seed=cfg.seed)
    n_layers = len(cfg.hidden) + 1
    params = train_binary_mlp(mcfg, xt, yt, steps=cfg.train_steps)
    params_np = {k: np.asarray(v) for k, v in params.items()}
    acts, _ = hard_forward(params_np, input_bits(xt).astype(np.uint8),
                           n_layers)
    raw_layers = [layer_graph(params_np[f"w{i}"], params_np[f"b{i}"],
                              acts[i], name=f"layer{i}", optimize="none")
                  for i in range(n_layers - 1)]
    ab_rows("nullanet", raw_layers, cfg.n_unit)

    # (b) random-graph stress: duplicate cones + dead fanout by design
    rng = np.random.default_rng(11)
    big = random_graph(rng, 64, 3000 if quick else 10_000, 48, locality=128)
    ab_rows("random", [big], 256)


# ---------------------------------------------------------------------------
# pipelining ablation (paper Fig. 8 a/b)
# ---------------------------------------------------------------------------

def bench_pipelining(quick: bool) -> None:
    rng = np.random.default_rng(1)
    g = random_graph(rng, 64, 3000, 32, locality=256)
    progs = [compile_graph(g, CompileSpec(n_unit=128, optimize="none"))
             ] * (8 if quick else 32)
    pipe = simulate_pipeline(progs, n_input_vectors=4096)
    seq = simulate_no_pipeline(progs, n_input_vectors=4096)
    row("fig8.pipelined", cycles_us(pipe.total_cycles),
        f"speedup={seq.total_cycles / pipe.total_cycles:.2f}x")
    row("fig8.sequential", cycles_us(seq.total_cycles), "")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write rows as JSON {name: {us, derived}}")
    args, _ = ap.parse_known_args()
    print("name,us_per_call,derived")
    t0 = time.time()
    bench_cost_model_validation(args.quick)
    bench_latency_split(args.quick)
    bench_pareto_search(args.quick)
    bench_nn_e2e(args.quick)
    bench_resources(args.quick)
    bench_pipelining(args.quick)
    bench_compile(args.quick)
    bench_opt(args.quick)
    bench_kernels(args.quick)
    bench_serve_logic(args.quick)
    bench_warm_start(args.quick)
    bench_verify(args.quick)
    bench_calibration(args.quick)
    bench_serve_traffic(args.quick)
    bench_flow_e2e(args.quick)
    print(f"# total {time.time() - t0:.1f}s, {len(ROWS)} rows")
    if args.json:
        doc = {name: {"us": round(us, 3), "derived": derived,
                      **({} if spec is None else {"spec": spec})}
               for name, us, derived, spec in ROWS}
        doc["bench_env"] = bench_env()
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json}")


if __name__ == "__main__":
    main()
