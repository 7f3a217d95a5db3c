"""host_ms: per call, the wall time of the call's ``bench.call`` span
less the device-busy time inside it, in milliseconds: the total over
all calls divided by the number of calls."""
from bench import profile


def read(run):
    if run.trace is None:
        return None
    spans = profile.spans_named(run.trace, "bench.call")
    if not spans:
        return None
    host = sum((b - a) - profile.busy_ns(run.trace, a, b)
               for a, b, _ in spans)
    return host / len(spans) * 1e-6
