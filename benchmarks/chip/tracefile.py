"""Reduction of a profiler trace to the benchmark's device numbers.

Input is the profiler's ``.xplane.pb``, read with
``jax.profiler.ProfileData`` and turned into plain tuples by
:func:`planes_of`; everything after that is plain Python over
``(name, start_ns, duration_ns)`` events, so tests feed it small
synthetic traces.

Per device (a plane named ``/device:<KIND>:<n>``) within the traced window
(from the ``bench.window.open`` marker for the window's seconds, or to the
``bench.window.close`` marker):

* busy: the union of the intervals in which an op of the device's op line
  (``XLA Ops``, or every line where the plane has none) runs;
* kernel: the summed time and count of the ops whose name holds the
  kernel's name (``logic_fabric``);
* the other ops, summed by name;
* idle time: each interval of the window with no op running, cut into
  pieces where a host event starts or ends, each piece labelled with the
  innermost benchmark span (``bench.*``) open on the host, or
  ``host: no benchmark span`` where none was, and within it the innermost
  event JAX recorded on that thread (``np.asarray(jax.Array)``, a wait for
  a result, for one).

Devices are reduced one by one; the caller averages them.
"""
from __future__ import annotations

import bisect
import glob
import re
from dataclasses import dataclass, field

OPEN, CLOSE = "bench.window.open", "bench.window.close"
KERNEL = "logic_fabric"
OP_LINE = "XLA Ops"
NO_SPAN = "host: no benchmark span"
_DEVICE = re.compile(r"^/device:([A-Z]+):(\d+)$")


@dataclass
class DeviceReduction:
    device: str
    window_ns: float
    busy_ns: float = 0.0
    kernel_ns: float = 0.0
    kernel_count: int = 0
    ops_ns: dict = field(default_factory=dict)     # op name -> ns
    gaps: list = field(default_factory=list)       # idle [(ns, label)]


def planes_of(path: str) -> list:
    """``[(plane name, [(line name, [(event, start_ns, dur_ns)])])]``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    return [(p.name, [(ln.name, [(e.name, float(e.start_ns),
                                  float(e.duration_ns)) for e in ln.events])
                      for ln in p.lines]) for p in pd.planes]


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(f"{log_dir}/plugins/profile/*/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def host_spans(planes) -> list:
    """``[(start, end, name)]`` of every host thread that holds a
    ``bench.*`` span: the benchmark's spans and JAX's own events there."""
    out = []
    for name, lines in planes:
        if name.startswith("/device:"):
            continue
        for _, events in lines:
            if any(n.startswith("bench.") for n, _, _ in events):
                out.extend((s, s + d, n) for n, s, d in events)
    return sorted(out)


def window_of(spans, window_s: float | None = None) -> tuple[float, float]:
    """The traced window: from the open marker to the close marker, or to
    ``window_s`` after the open marker where that is given (a close
    marker waits for the event loop, which a stall delays)."""
    opens = [s for s, _, n in spans if n == OPEN]
    closes = [s for s, _, n in spans if n == CLOSE]
    if not opens or not closes or max(closes) <= min(opens):
        raise ValueError("the trace has no bench.window.open/close markers")
    lo = min(opens)
    return lo, (max(closes) if window_s is None else lo + window_s * 1e9)


def _union(intervals, lo: float, hi: float) -> list:
    """Merged ``[(start, end)]`` of ``intervals`` clipped to [lo, hi]."""
    merged: list = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _label(spans, starts, longest: float, t: float) -> str:
    """What the host was doing at time ``t``: the innermost ``bench.*``
    span open then, and the innermost other event inside it, if any (only
    spans that start within ``longest`` before ``t`` can be open)."""
    bench = inner = None
    j = bisect.bisect_right(starts, t) - 1
    while j >= 0 and starts[j] >= t - longest:
        s, e, n = spans[j]
        j -= 1
        if t >= e:
            continue
        if n.startswith("bench."):
            if bench is None or e - s < bench[1] - bench[0]:
                bench = (s, e, n)
        elif inner is None or e - s < inner[1] - inner[0]:
            inner = (s, e, n)
    if bench is None:
        return NO_SPAN
    if inner is not None and bench[0] <= inner[0] and inner[1] <= bench[1]:
        return f"{bench[2]} > {inner[2]}"
    return bench[2]


def _pieces(spans, starts, longest: float, s: float, e: float) -> list:
    """The idle interval [s, e] cut where a host event starts or ends,
    each piece labelled (``_label``), neighbours of one label merged."""
    cuts = {s, e}
    lo = bisect.bisect_left(starts, s - longest)
    for a, b, _ in spans[lo:bisect.bisect_right(starts, e)]:
        cuts.update(t for t in (a, b) if s < t < e)
    out: list = []
    cuts = sorted(cuts)
    for a, b in zip(cuts, cuts[1:]):
        label = _label(spans, starts, longest, (a + b) / 2)
        if out and out[-1][1] == label:
            out[-1] = (out[-1][0] + b - a, label)
        else:
            out.append((b - a, label))
    return out


def reduce_planes(planes, window_s: float | None = None,
                  kernel: str = KERNEL) -> list[DeviceReduction]:
    spans = host_spans(planes)
    lo, hi = window_of(spans, window_s)
    spans = [sp for sp in spans if sp[2] not in (OPEN, CLOSE)]
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    out = []
    for name, lines in planes:
        if not _DEVICE.match(name):
            continue
        op_lines = [ev for ln, ev in lines if ln == OP_LINE] or \
            [ev for _, ev in lines]
        events = [e for ev in op_lines for e in ev]
        red = DeviceReduction(device=name, window_ns=hi - lo)
        busy = _union([(s, s + d) for _, s, d in events], lo, hi)
        red.busy_ns = sum(e - s for s, e in busy)
        for n, s, d in events:
            d = max(0.0, min(s + d, hi) - max(s, lo))
            if d <= 0:
                continue
            red.ops_ns[n] = red.ops_ns.get(n, 0.0) + d
            if kernel in n:
                red.kernel_ns += d
                red.kernel_count += 1
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                red.gaps.extend(_pieces(spans, starts, longest, s, e))
        out.append(red)
    return out


def breakdown(reds: list[DeviceReduction], top: int = 10) -> dict:
    """The result line's ``breakdown``: the device ops that took most
    time, and the device's idle time by what the host was doing (with the
    number of idle pieces and the longest), in seconds averaged over the
    devices."""
    n = max(1, len(reds))
    ops: dict = {}
    idle: dict = {}
    for r in reds:
        for k, v in r.ops_ns.items():
            ops[k] = ops.get(k, 0.0) + v
        for ns, label in r.gaps:
            tot, cnt, big = idle.get(label, (0.0, 0, 0.0))
            idle[label] = (tot + ns, cnt + 1, max(big, ns))
    short: dict = {}
    for k, v in ops.items():            # an op's HLO text, by its name
        name = k.split(" = ")[0]
        short[name] = short.get(name, 0.0) + v
    dev = sorted(short.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1][0])[:top]
    return {"device_ops": [[k, v / n / 1e9] for k, v in dev],
            "idle_gaps": [[f"{k} ({c / n:g} pieces, longest {b / 1e6:g} ms)",
                           t / n / 1e9] for k, (t, c, b) in gaps]}
