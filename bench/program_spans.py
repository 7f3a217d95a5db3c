#!/usr/bin/env python3
"""Where a call's host time goes, from the program's own spans.

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds <s>

Runs the cell as ``bench/run.py --trace 1`` does and prints its result
line, then one more JSON line: per call, the host milliseconds in each
phase of ``FleetEngine.run_many``, the share of the sweep's device runs
that lie inside a ``fleet.sweep`` span, the longest device-idle gaps
named by the innermost span of the benchmark or the program, and the
program's counters over the run (set-up's warm-up call included).

The program (``repro.core.telemetry``) records ``fleet.*`` spans with
their stats, and a ``py.gc`` span for each garbage collection, on the
host plane of the same trace as the device's operations.
``bench/profile.py`` keeps only the benchmark's ``bench.*`` spans, so
none of this is a metric of the benchmark yet; this file holds the
reduction until it is.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import sys
import time
import warnings
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

T_START = time.perf_counter()

if __name__ == "__main__":
    ROOT = Path(__file__).resolve().parents[1]
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, profile  # noqa: E402

#: start_ns, end_ns, name, stats
Span = Tuple[float, float, str, Dict[str, object]]

PREFIX = "fleet."
GC_SPAN = "py.gc"
#: the phases of a call; what none of them (nor a device operation)
#: covers is ``call_other``: routing, the candidate arrays, and the
#: caller's reads of the reports
PHASES = ("fleet.surface", "fleet.price", "fleet.sweep", "fleet.assemble",
          "fleet.cell", GC_SPAN)


def read(log_dir: str) -> List[Span]:
    """The program's spans in the one ``.xplane.pb`` under ``log_dir``,
    sorted by start."""
    from jax.profiler import ProfileData

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file, found {paths}")
    data = ProfileData.from_file(paths[0])
    out: List[Span] = []
    # jaxlib builds the stats' type on first use, with a warning that
    # aborts the process where warnings are errors
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in data.planes:
            if plane.name.startswith("/device:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX) or e.name == GC_SPAN:
                        a = float(e.start_ns)
                        out.append((a, a + float(e.duration_ns), e.name,
                                    {k: v for k, v in e.stats}))
    return sorted(out, key=lambda s: (s[0], -s[1]))


def named(trace: profile.Trace, program: Sequence[Span],
          name: str) -> List[Span]:
    """The spans called ``name`` that lie inside the window."""
    lo, hi = trace.window
    return [s for s in program
            if s[2] == name and lo <= s[0] and s[1] <= hi]


def ms_per_call(trace: profile.Trace, program: Sequence[Span], name: str,
                less_busy: bool = False) -> Optional[float]:
    """Milliseconds per call (``bench.call`` span) in the spans called
    ``name``, less the device-busy time inside them if ``less_busy``;
    ``None`` for a program that recorded no ``fleet.run_many``."""
    calls = profile.spans_named(trace, "bench.call")
    if not calls or not named(trace, program, "fleet.run_many"):
        return None
    total = 0.0
    for a, b, _, _ in named(trace, program, name):
        total += (b - a) - (profile.busy_ns(trace, a, b) if less_busy
                            else 0.0)
    return total / len(calls) * 1e-6


def call_other_ms(trace: profile.Trace,
                  program: Sequence[Span]) -> Optional[float]:
    """Milliseconds per call of ``bench.call`` that no phase span and no
    device operation covers."""
    calls = profile.spans_named(trace, "bench.call")
    if not calls or not named(trace, program, "fleet.run_many"):
        return None
    phases = [s[:3] for s in program if s[2] in PHASES]
    phases += [(a, b, "") for plane in trace.busy for a, b in plane]
    covered = profile.merge(phases)
    other = sum((b - a) - profile.covered(covered, a, b)
                for a, b, _ in calls)
    return other / len(calls) * 1e-6


def sweep_inside_share(trace: profile.Trace,
                       program: Sequence[Span]) -> Optional[float]:
    """The share of the window's sweep runs on the device that lie
    inside a ``fleet.sweep`` span: 1.0 where the spans share the device
    trace's clock."""
    runs = profile.program_runs(trace, "sweep")
    if not runs:
        return None
    sweeps = profile.merge([s[:3] for s in named(trace, program,
                                                 "fleet.sweep")])
    inside = sum(profile.covered(sweeps, a, b) >= b - a for a, b in runs)
    return inside / len(runs)


def breakdown(trace: profile.Trace, program: Sequence[Span],
              top: int = 10) -> Dict[str, list]:
    """``profile.breakdown`` with each idle gap named by the innermost
    span, the benchmark's or the program's."""
    both = dataclasses.replace(
        trace, spans=sorted(trace.spans + [s[:3] for s in program]))
    return profile.breakdown(both, top)


def split(trace: profile.Trace, program: Sequence[Span]) -> Dict:
    """Per call, milliseconds of host time in each phase, and what the
    trace says about the rest."""
    calls = profile.spans_named(trace, "bench.call")
    host = sum((b - a) - profile.busy_ns(trace, a, b)
               for a, b, _ in calls) / max(len(calls), 1) * 1e-6
    return {
        "calls": len(calls),
        "host_ms": host,
        "surface_ms": ms_per_call(trace, program, "fleet.surface"),
        "pricing_ms": ms_per_call(trace, program, "fleet.price"),
        "sweep_host_ms": ms_per_call(trace, program, "fleet.sweep",
                                     less_busy=True),
        "fetch_ms": ms_per_call(trace, program, "fleet.fetch",
                                less_busy=True),
        "assembly_ms": ms_per_call(trace, program, "fleet.assemble",
                                   less_busy=True),
        "ledger_ms": ms_per_call(trace, program, "fleet.ledger"),
        "cell_ms": ms_per_call(trace, program, "fleet.cell"),
        "gc_ms": ms_per_call(trace, program, GC_SPAN),
        "call_other_ms": call_other_ms(trace, program),
        "sweep_inside_share": sweep_inside_share(trace, program),
        "collections": len(named(trace, program, GC_SPAN)),
        "breakdown": breakdown(trace, program),
    }


def counters() -> Optional[Dict[str, int]]:
    """The program's counters; ``None`` for a program that keeps
    none."""
    try:
        from repro.core import telemetry
    except ImportError:
        return None
    return telemetry.counters()


def traced_run(cell: harness.Cell, seed: int, seconds: float,
               device: Dict, t_start: float) -> Tuple[Dict, Dict]:
    """The harness's traced run of ``cell`` and the split of its calls
    (with the counters' change over the run)."""
    # the harness removes its trace once read: read the program's spans
    # from it at that moment
    recorded = {}
    read_bench = profile.read

    def read_both(log_dir: str) -> profile.Trace:
        recorded["trace"] = read_bench(log_dir)
        recorded["program"] = read(log_dir)
        return recorded["trace"]

    before = counters()
    profile.read = read_both
    try:
        result = harness.run_cell(cell, seed, seconds, True, device,
                                  t_start)
    finally:
        profile.read = read_bench
    after = counters()
    out = split(recorded["trace"], recorded["program"])
    if after is not None:
        out["counters"] = {k: v - before.get(k, 0)
                           for k, v in sorted(after.items())}
    out["device"] = device
    return result, out


def main(argv: Optional[List[str]] = None,
         t_start: Optional[float] = None) -> int:
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    device = harness.accelerator(cell.chips)
    result, out = traced_run(cell, args.seed, args.seconds, device, t_start)
    print(json.dumps(result))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
