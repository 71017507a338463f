"""One run of one cell: set-up, the measured window, the trace reduction,
the comparison with the plain reference, and the result line.

Set-up: the configuration's netlist (committed, checked by fingerprint),
the compiled program (the system's own ``ArtifactStore`` under
``.cache/store``: compiled once per checkout, then loaded), the fused
runner's executable (JAX's persistent compile cache under ``.cache/jax``),
the seeded schedule of requests and their payloads, and a warm-up of the
cell's one runner shape. Then the window drives ``FrontDoor.submit`` for
``seconds``; every answer is awaited; with ``trace`` the window is traced.
Once the window has closed and the device memory has been read, a seeded
sample of the answered requests, the longest among them, is compared bit
by bit with the plain reference (``reference.py``).
"""
from __future__ import annotations

import asyncio
import gc
import math
import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path

import numpy as np

from benchmarks.chip import loadgen, netlist, tracefile
from benchmarks.chip.cells import Cell, bench_dir
from benchmarks.chip.reference import Reference, control
from benchmarks.chip.spans import Spans, TracedEngine

_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
#: warm-up waves sent through the front door after the compile
WARM_WAVES = 3


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class CompileCounter:
    """Traces, backend compiles and persistent-cache hits and misses
    counted from JAX's monitoring events while it is open."""

    def __init__(self):
        import jax.monitoring as mon
        self._mon = mon
        self.counts = {"traces": 0, "compiles": 0, "cache_hits": 0,
                       "cache_misses": 0}
        mon.register_event_listener(self._event)
        mon.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == _HIT:
            self.counts["cache_hits"] += 1
        elif event == _MISS:
            self.counts["cache_misses"] += 1

    def _duration(self, event: str, _secs: float, **_kw) -> None:
        if event == _TRACE:
            self.counts["traces"] += 1
        elif event == _COMPILE:
            self.counts["compiles"] += 1

    def snapshot(self) -> dict:
        from repro.kernels.logic_dsp import kernel
        return {**self.counts, "kernel_launch_traces": kernel.launch_count()}

    def close(self) -> None:
        self._mon.unregister_event_listener(self._event)
        self._mon.unregister_event_duration_listener(self._duration)


class GcWatch:
    """Python's garbage collections while it is open: (generation,
    seconds) of each."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []
        self._t = 0.0
        gc.callbacks.append(self._cb)

    def _cb(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def close(self) -> None:
        gc.callbacks.remove(self._cb)


def stall_report(step_s, step_cpu_s, pauses) -> str:
    """The window's longest waves with their CPU seconds, and its garbage
    collections: where a throughput dip comes from."""
    wall = np.asarray(step_s, float)
    cpu = np.asarray(step_cpu_s[:len(wall)], float)
    top = np.argsort(wall)[::-1][:5]
    longest = ", ".join(f"{wall[i] * 1e3:.1f}/{cpu[i] * 1e3:.1f}"
                        for i in top)
    gen2 = sum(1 for g, _ in pauses if g == 2)
    gc_ms = [d * 1e3 for _, d in pauses] or [0.0]
    return (f"steps: median {np.median(wall) * 1e3 if wall.size else 0:.3f}"
            f" ms, {int((wall > 0.02).sum())} over 20 ms, longest (wall/cpu "
            f"ms) {longest}; gc: {len(pauses)} collections, {gen2} of "
            f"generation 2, longest {max(gc_ms):.1f} ms, total "
            f"{sum(gc_ms):.1f} ms")


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


@dataclass
class Setup:
    """What set-up made: the netlist, the engine and the schedule."""

    netlist: netlist.Netlist
    graph: object
    engine: TracedEngine
    schedule: loadgen.Schedule
    phases: dict


def build_graph(nl: netlist.Netlist, name: str):
    from repro.core.gate_ir import LogicGraph
    return LogicGraph(nl.n_inputs, gates=[tuple(g) for g in nl.gates.tolist()],
                      outputs=nl.outputs.tolist(), name=name)


def make_engine(cell: Cell, devices, root: Path, spans: Spans,
                engine_cls=TracedEngine) -> TracedEngine:
    from jax.sharding import Mesh

    from repro.core.artifact_store import ArtifactStore
    from repro.core.spec import CompileSpec

    cfg = cell.config
    sharded = cell.chips > 1
    return engine_cls(
        CompileSpec(n_unit=int(cfg["n_unit"])),
        capacity=int(cfg["capacity_per_device"]) * cell.chips,
        shard=sharded,
        mesh=Mesh(np.asarray(devices[:cell.chips]), ("data",)) if sharded
        else None,
        store=ArtifactStore(bench_dir(root) / ".cache" / "store"),
        spans=spans)


def setup(cell: Cell, seed: int, seconds: float, devices, root: Path,
          spans: Spans, counter: CompileCounter, engine_cls) -> Setup:
    phases = {}
    nl, phases["netlist_s"] = netlist.of_config(root, cell.config)
    graph = build_graph(nl, cell.config["name"])
    log(f"netlist loaded: {nl.n_gates} gates, "
        f"{nl.n_inputs} inputs, {nl.n_outputs} outputs, fingerprint "
        f"{nl.fingerprint}")
    engine = make_engine(cell, devices, root, spans, engine_cls)
    t0 = time.perf_counter()
    entry = engine.cache.get(graph, engine.spec)
    phases["program_s"] = time.perf_counter() - t0
    st = engine.cache.stats()
    log(f"program: {sum(p.n_steps for p in entry.programs)} steps, n_addr "
        f"{max(p.n_addr for p in entry.programs)}, "
        f"{len(entry.programs)} program(s); store hits {st['store_hits']}, "
        f"compiles {st['compiles']}")
    t0 = time.perf_counter()
    sched = loadgen.make_schedule(cell.traffic, seed, seconds, nl.n_inputs)
    phases["schedule_s"] = time.perf_counter() - t0
    before = counter.snapshot()
    t0 = time.perf_counter()
    engine.serve(graph, sched.pool[:engine.capacity])
    phases["xla_s"] = time.perf_counter() - t0
    phases["xla"] = delta(before, counter.snapshot())
    return Setup(nl, graph, engine, sched, phases)


async def serve_window(cell: Cell, s: Setup, seconds: float, trace_dir,
                  spans: Spans, counter: CompileCounter):
    """Front-door warm-up, then the window (traced when asked)."""
    import jax

    from repro.serve.frontdoor import FrontDoor

    tenant = cell.config["name"]
    traffic = cell.traffic
    engine = s.engine
    door = FrontDoor(engine, max_queue=int(traffic["max_queue"]),
                     default_deadline_s=float(traffic["deadline_s"]))
    door.register(tenant, s.graph)
    async with door:
        t0 = time.perf_counter()
        warm = s.schedule.pool[:engine.capacity]
        for _ in range(WARM_WAVES):
            await door.submit(tenant, warm)
        s.phases["warmup_s"] = time.perf_counter() - t0
        door.reset_metrics()
        engine.reset_telemetry()
        engine.step_s.clear()
        engine.step_cpu_s.clear()
        engine.submit_t.clear()
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir), profiler_options=_opts())
        before = counter.snapshot()
        at_close = {}

        def close() -> None:
            at_close.update(waves=engine.invocations,
                            steps=len(engine.step_s))
            with spans(tracefile.CLOSE):
                pass

        with spans(tracefile.OPEN):
            pass
        asyncio.get_running_loop().call_later(seconds, close)
        watch = GcWatch()
        try:
            out = await loadgen.drive(door, tenant, s.schedule, traffic,
                                      seconds, engine, spans)
        finally:
            watch.close()
        # an open loop may be answered before its window closes: the
        # close (and its marker in the trace) still has to come
        await asyncio.sleep(max(0.0, out.t_close + 0.01 - time.perf_counter()))
        in_window = delta(before, counter.snapshot())
        waves = at_close["waves"]
        step_s = engine.step_s[:at_close["steps"]]
        if trace_dir is not None:
            jax.profiler.stop_trace()
    log(stall_report(step_s, engine.step_cpu_s, watch.pauses))
    return out, in_window, waves, step_s, door.metrics()


def _opts():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def compare(nl: netlist.Netlist, sched: loadgen.Schedule,
            out: loadgen.Outcome, use_control: bool = False) -> dict:
    """Served bits of the kept requests (and the longest) against the
    plain reference; with ``use_control``, the control's bits on the same
    inputs in place of the served ones."""
    kept = dict(out.kept)
    if out.longest[2] is not None:
        kept[out.longest[1]] = out.longest[2]
    order = sorted(kept)
    mismatches = compared = 0
    if order:
        inputs = [sched.payload(i) for i in order]
        x = np.concatenate(inputs)
        want = Reference.of(nl).evaluate(x)
        compared = int(want.size)
        if use_control:
            mismatches = int(np.count_nonzero(control(nl.layers, x) != want))
        else:
            lo = 0
            for i, xi in zip(order, inputs):
                w, got = want[lo:lo + len(xi)], kept[i]
                lo += len(xi)
                mismatches += (w.size if got.shape != w.shape
                               else int(np.count_nonzero(got != w)))
    done = np.asarray(out.done, float)
    unanswered = sum(1 for r, i in enumerate(out.index)
                     if math.isnan(done[r]) and i not in out.failed)
    return {"requests_compared": len(order), "bits_compared": compared,
            "bit_mismatches": mismatches, "unanswered": unanswered}


def checks_of(cmp: dict) -> dict:
    """The numbers compared, each beside its limit."""
    return {"bit_mismatches": {"value": cmp["bit_mismatches"], "limit": 0},
            "unanswered": {"value": cmp["unanswered"], "limit": 0},
            "bits_compared": {"value": cmp["bits_compared"], "min": 1}}


def is_correct(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] if "limit" in c
               else c["value"] >= c["min"] for c in checks.values())


def device_info(devices, chips: int) -> dict:
    used = devices[:chips]
    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             root: Path, devices, t_start: float, peaks: dict | None = None,
             engine_cls=TracedEngine, with_control: bool = False) -> dict:
    """One run of ``cell``; returns the result line as a dict. With
    ``with_control`` it also reads the control on the same sample, under
    ``control`` (the benchmark's own runs never do)."""
    spans = Spans(trace)
    counter = CompileCounter()
    try:
        s = setup(cell, seed, seconds, devices, root, spans, counter,
                  engine_cls)
        trace_dir = None
        if trace:
            trace_dir = bench_dir(root) / ".cache" / "trace" / cell.name
            shutil.rmtree(trace_dir, ignore_errors=True)
        out, in_window, waves, step_s, door_m = asyncio.run(
            serve_window(cell, s, seconds, trace_dir, spans, counter))
    finally:
        counter.close()
    ph = s.phases
    setup_s = out.t0 - t_start
    log(f"setup_s={setup_s:.3f}: netlist {ph['netlist_s']:.3f}, program "
        f"{ph['program_s']:.3f}, schedule {ph['schedule_s']:.3f}, xla "
        f"{ph['xla_s']:.3f} {ph['xla']}, warm-up {ph['warmup_s']:.3f}")
    log(f"in window: {in_window} (all should be 0)")
    device = device_info(devices, cell.chips)
    reds = None
    if trace:
        reds = tracefile.reduce_planes(tracefile.planes_of(
            tracefile.find_xplane(str(trace_dir))), out.t_close - out.t0)
        if not reds:
            raise RuntimeError("the trace holds no device plane")
        device["busy_s"] = float(np.mean([r.busy_ns for r in reds])) / 1e9
        device["window_s"] = float(np.mean([r.window_ns for r in reds])) / 1e9
    sent = np.asarray(out.sent) - np.asarray(out.due) if out.index else \
        np.zeros(1)
    log(f"sends late by: p50 {np.percentile(sent, 50) * 1e3:.3f} ms, p99 "
        f"{np.percentile(sent, 99) * 1e3:.3f} ms, max "
        f"{sent.max() * 1e3:.3f} ms; waves {waves}; front door "
        f"{door_m['offered']} offered, {door_m['completed']} completed, "
        f"shed {door_m['shed_by_code']}")
    done = np.asarray(out.done, float) - out.t0
    per_s = np.bincount(done[done < seconds].astype(int),
                        weights=np.asarray(out.n, float)[done < seconds],
                        minlength=int(math.ceil(seconds)))
    log(f"samples completed per second of the window: "
        f"{per_s.astype(int).tolist()}; mean occupancy "
        f"{door_m['engine']['mean_occupancy']:.4f}")
    graph_gates = s.graph.n_gates
    engine_cap = s.engine.capacity
    del s.engine, door_m
    gc.collect()
    cmp = compare(s.netlist, s.schedule, out)
    checks = checks_of(cmp)
    run = {
        "cell": cell.name, "chips": cell.chips, "seconds": seconds,
        "setup_s": setup_s, "window_s": out.t_close - out.t0,
        "t0": out.t0, "t_close": out.t_close, "drain_s": loadgen.DRAIN_S,
        "n": np.asarray(out.n, float), "due": np.asarray(out.due, float),
        "submit": np.asarray(out.submit, float),
        "done": np.asarray(out.done, float),
        "gates": graph_gates, "n_inputs": s.netlist.n_inputs,
        "n_outputs": s.netlist.n_outputs, "capacity": engine_cap,
        "waves": waves, "step_s": np.asarray(step_s, float),
        "trace": reds, "peaks": (peaks or {}).get(device["kind"]),
    }
    metrics = {}
    for m in cell.metrics(trace):
        v = m.read(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    result = {"correct": is_correct(checks), "attempted": out.attempted,
              "failed": len(out.failed) + cmp["unanswered"],
              "metrics": metrics, "device": device}
    if reds is not None:
        result["breakdown"] = tracefile.breakdown(reds)
    log(f"compared {cmp['requests_compared']} requests, "
        f"{cmp['bits_compared']} bits")
    if with_control:
        result["control"] = checks_of(compare(s.netlist, s.schedule, out,
                                              use_control=True))
    result["checks"] = checks
    return result
