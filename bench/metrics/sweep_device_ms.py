"""sweep_device_ms: device milliseconds per call of the fast plane's
longest-path sweep, the compiled program whose name holds ``sweep``
(``jit_sweep``), summed over its runs in the window."""
from bench import profile


def read(run):
    if run.trace is None:
        return None
    runs = profile.program_runs(run.trace, "sweep")
    calls = profile.spans_named(run.trace, "bench.call")
    if not runs or not calls:
        return None
    return sum(b - a for a, b in runs) / len(calls) * 1e-6
