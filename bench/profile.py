"""From a profiler trace to device busy time, idle gaps and spans.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it. Device planes are named ``/device:<KIND>:<n>``; on each, the
``XLA Ops`` line holds the operations that ran and the ``XLA Modules``
line the compiled programs they belong to. The benchmark's own
``TraceAnnotation`` spans (``bench.*``) lie on the host plane, on the
thread that made them. All start times share one clock (nanoseconds).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]        # start_ns, end_ns, name

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Trace:
    """What the metrics read from one traced window."""

    window: Tuple[float, float]
    ops: List[List[Interval]]       # per device plane: XLA Ops
    modules: List[List[Interval]]   # per device plane: XLA Modules
    spans: List[Interval]           # the benchmark's host spans
    #: per device plane, the union of its ops' intervals, sorted
    busy: List[List[Tuple[float, float]]] = dataclasses.field(init=False)

    def __post_init__(self):
        self.busy = [merge(p) for p in self.ops]


def read(log_dir: str) -> Trace:
    """Parse the one ``.xplane.pb`` the profiler wrote under ``log_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    data = ProfileData.from_file(paths[0])
    ops: List[List[Interval]] = []
    modules: List[List[Interval]] = []
    spans: List[Interval] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        plane_ops: List[Interval] = []
        plane_modules: List[Interval] = []
        for line in plane.lines:
            if device and line.name == "XLA Ops":
                plane_ops.extend(_events(line))
            elif device and line.name == "XLA Modules":
                plane_modules.extend(_events(line))
            elif not device:
                spans.extend(_events(line, SPAN_PREFIX))
        if device and (plane_ops or plane_modules):
            ops.append(plane_ops)
            modules.append(plane_modules)
    return from_intervals(ops, modules, spans)


def _events(line, prefix: str = "") -> List[Interval]:
    """The line's events whose name starts with ``prefix``. A device
    op's name is its HLO text (``%fusion.31 = f32[...] fusion(...)``);
    only the part before `` = `` is kept."""
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name.split(" = ", 1)[0]) for e in line.events
            if e.name.startswith(prefix)]


def from_intervals(ops: List[List[Interval]], modules: List[List[Interval]],
                   spans: List[Interval]) -> Trace:
    """A trace from recorded intervals; its window is the benchmark's
    ``bench.window`` span."""
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise RuntimeError(f"expected one {WINDOW_SPAN} span, "
                           f"found {len(windows)}")
    return Trace(window=windows[0][:2], ops=ops, modules=modules,
                 spans=sorted(spans))


def merge(intervals: Sequence[Interval], lo: float = float("-inf"),
          hi: float = float("inf")) -> List[Tuple[float, float]]:
    """Union of intervals, clipped to [lo, hi], sorted."""
    out: List[List[float]] = []
    for a, b, _ in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def covered(merged: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Length of [lo, hi] that the sorted, disjoint intervals cover."""
    k = max(bisect.bisect_right(merged, (lo, float("inf"))) - 1, 0)
    total = 0.0
    for a, b in merged[k:]:
        if a >= hi:
            break
        total += max(0.0, min(b, hi) - max(a, lo))
    return total


def busy_ns(trace: Trace, lo: Optional[float] = None,
            hi: Optional[float] = None) -> float:
    """Device-busy nanoseconds inside [lo, hi] (default: the window),
    averaged over the device planes; 0 where no operation ran."""
    lo = trace.window[0] if lo is None else lo
    hi = trace.window[1] if hi is None else hi
    if not trace.busy:
        return 0.0
    return sum(covered(b, lo, hi) for b in trace.busy) / len(trace.busy)


def window_ns(trace: Trace) -> float:
    return trace.window[1] - trace.window[0]


def spans_named(trace: Trace, name: str) -> List[Interval]:
    return [s for s in trace.spans if s[2] == name]


def program_runs(trace: Trace, part: str) -> List[Tuple[float, float]]:
    """Runs, inside the window on the first device, of the compiled
    programs whose name holds ``part``."""
    lo, hi = trace.window
    if not trace.modules:
        return []
    return [(a, b) for a, b, name in trace.modules[0]
            if part in name and lo <= a and b <= hi]


def host_activity(trace: Trace, t: float) -> str:
    """The innermost benchmark span around ``t`` (the window itself
    when the host was between spans)."""
    inner = [s for s in trace.spans if s[0] <= t <= s[1]]
    if not inner:
        return "outside bench spans"
    return min(inner, key=lambda s: s[1] - s[0])[2]


def breakdown(trace: Trace, top: int = 10) -> Dict[str, list]:
    """The device operations that took most time in the window (summed
    by name, over all device planes) and the longest idle gaps of the
    first device, each named by what the host was doing."""
    lo, hi = trace.window
    by_op: Dict[str, float] = {}
    for plane in trace.ops:
        for a, b, name in plane:
            d = min(b, hi) - max(a, lo)
            if d > 0:
                by_op[name] = by_op.get(name, 0.0) + d
    device_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    busy = merge([(a, b, "") for a, b in trace.busy[0]], lo, hi) \
        if trace.busy else []
    gaps, t = [], lo
    for a, b in busy:
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[host_activity(trace, (a + b) / 2), (b - a) * 1e-9]
            for a, b in gaps[:top]]
    return {"device_ops": [[n, s * 1e-9] for n, s in device_ops],
            "idle_gaps": idle}
