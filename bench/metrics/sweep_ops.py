"""sweep_ops: device operations per run of the fast plane's sweep: the
operations on the first device that start inside a run of the compiled
program whose name holds ``sweep`` (``jit_sweep``), inside the window,
over the number of such runs. The device's face of the program's
``fleet.sweep.blocks``: it follows the sweep's ranks and gather blocks,
and should not grow with an in-degree."""
import bisect

from bench import profile


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    runs = profile.program_runs(run.trace, "sweep")
    if not runs:
        return None
    starts = sorted(a for a, _, _ in run.trace.ops[0])
    inside = sum(bisect.bisect_left(starts, b) - bisect.bisect_left(starts, a)
                 for a, b in runs)
    return inside / len(runs)
