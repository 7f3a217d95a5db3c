"""sweep_roofline: the sweep's share of its roofline, in percent: the
least time the chip could take for what the sweep's result needs, over
the sweep's device time.

The count is of the work the result needs, whatever implements it. The
sweep turns C candidates' runtimes into the finish time of each of N
instances under each candidate: it has to read the N arrivals, the C x V
runtimes and the E edges once, and write the C x N finishes once, at 8
bytes each (float64, and an edge as two int32 endpoints). The padded
(C, N, max predecessors) gather of today's program is not counted. The
operations, one add per function and one max per edge for each of the
C x N instances, at the chip's bf16 peak, bound it far less than the
bytes do; the larger of the two times is taken."""
from bench import profile
from bench.harness import peaks


def least_bytes(c: int, n: int, v: int, e: int) -> int:
    return 8 * (n + c * v + e + c * n)


def least_ops(c: int, n: int, v: int, e: int) -> int:
    return c * n * (v + e)


def read(run):
    if run.trace is None:
        return None
    runs = profile.program_runs(run.trace, "sweep")
    if not runs:
        return None
    peak = peaks(run.device["kind"])
    mix, config = run.cell.mix, run.cell.config
    c = int(mix["candidates"]["count"])
    n = int(mix["arrivals"]["count"])
    v, e = len(config["functions"]), len(config["edges"])
    least_s = max(least_bytes(c, n, v, e) / peak["hbm_bytes_per_s"],
                  least_ops(c, n, v, e) / peak["bf16_flops_per_s"])
    device_s = sum(b - a for a, b in runs) * 1e-9
    return least_s * len(runs) / device_s * 100.0
