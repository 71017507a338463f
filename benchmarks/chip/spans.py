"""The benchmark's spans: host-clock records and profiler annotations
around the calls into each layer, kept in the benchmark's own files.

``TracedEngine`` is a thin :class:`~repro.serve.LogicEngine` subclass: it
times ``submit`` (per request, keyed by the payload array the front door
hands down) and ``step`` (per wave, including the block on the wave's
result), and wraps the fused runner call. With tracing on, each of those is
also a ``jax.profiler.TraceAnnotation``, so the trace reduction can say
what the host was doing while the device sat idle.
"""
from __future__ import annotations

import contextlib
import time

from repro.serve import LogicEngine

SUBMIT, STEP, RUNNER = "bench.engine.submit", "bench.engine.step", \
    "bench.engine.runner"
SEND, COMPLETE = "bench.loadgen.send", "bench.loadgen.complete"


class Spans:
    """Annotation factory: real ``TraceAnnotation`` spans while a trace
    is being taken, no-ops otherwise."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        if enabled:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation

    def __call__(self, name: str):
        return self._ann(name) if self.enabled else contextlib.nullcontext()


class TracedEngine(LogicEngine):
    """LogicEngine with the benchmark's spans around submit, step and
    the runner call. ``submit_t`` maps ``id(payload)`` to the host time
    of the front door's call of ``submit``; ``step_s`` collects the host
    seconds of each wave's ``step``, and ``step_cpu_s`` the process's CPU
    seconds over the same span (a long step with little CPU waited)."""

    def __init__(self, *args, spans: Spans | None = None, **kw):
        super().__init__(*args, **kw)
        self.spans = spans or Spans(False)
        self.submit_t: dict[int, float] = {}
        self.step_s: list[float] = []
        self.step_cpu_s: list[float] = []

    def submit(self, graph, bits):
        self.submit_t[id(bits)] = time.perf_counter()
        with self.spans(SUBMIT):
            return super().submit(graph, bits)

    def step(self):
        t0, c0 = time.perf_counter(), time.process_time()
        with self.spans(STEP):
            done = super().step()
        self.step_s.append(time.perf_counter() - t0)
        self.step_cpu_s.append(time.process_time() - c0)
        return done

    def _build_runner(self, entry):
        run = super()._build_runner(entry)
        spans = self.spans

        def runner(bits):
            with spans(RUNNER):
                return run(bits)

        return runner
