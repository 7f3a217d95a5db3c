#!/usr/bin/env python3
"""The readings that a cell's correctness limits are set from.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 ... 12

In one process on the chip: for each seed, a short window of the cell's
own calls at its own sizes, sampled and compared with the plain reference
exactly as a run does (the program's reading, the lower one); then, for
the first ``--control-seeds`` seeds, the same sampled calls answered by
the reference computed in float32, one precision below what the
configuration states, put in the program's place (the control's reading,
the upper one). Prints one JSON line per seed and reading, then the
largest program reading and the smallest control reading of each
number. The benchmark's own runs never run this.
"""
import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import generate, harness, reference  # noqa: E402

#: the precision below the configuration's, for the control
LOWER = {"float64": np.float32}


def control_reading(cell: harness.Cell, sampled) -> dict:
    """The reference in the lower precision, in the program's place."""
    lower = LOWER[cell.config["precision"]]
    graph = reference.Graph.of(cell.config)
    swapped = [harness.Sampled(s.inputs,
                               harness.want(cell, graph, s.inputs, lower))
               for s in sampled]
    return harness.check(cell, swapped)


def readings(cell: harness.Cell, system: harness.System, seeds, seconds,
             control_seeds: int):
    for k, seed in enumerate(seeds):
        rng = np.random.default_rng([seed % 2**64, 0, 1])
        calls, sampled, failed = harness.window(cell, system, seed, seconds,
                                                rng)
        row = {"seed": seed, "calls": len(calls), "failed": failed,
               "program": harness.check(cell, sampled)}
        if k < control_seeds:
            row["control"] = control_reading(cell, sampled)
        yield row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    device = harness.accelerator(cell.chips)
    harness.use_compile_cache()
    system = harness.System(cell)
    warm = generate.draw_call(cell.mix, cell.config, args.seeds[0], 0)
    system.call(system.configs(warm), warm.arrivals)
    worst: dict = {}
    least: dict = {}
    for row in readings(cell, system, args.seeds, args.seconds,
                        args.control_seeds):
        print(json.dumps(dict(row, workload=cell.name,
                              device=device["kind"])), flush=True)
        for k, v in row["program"].items():
            worst[k] = max(worst.get(k, 0), v)
        for k, v in row.get("control", {}).items():
            least[k] = min(least.get(k, float("inf")), v)
    print(json.dumps({"workload": cell.name, "device": device["kind"],
                      "program_max": worst, "control_min": least,
                      "limits": cell.config["check_limits"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
