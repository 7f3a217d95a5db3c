"""surface_ms: host milliseconds per call inside the backend's
response-surface evaluation (``invoke_config_batch``), timed by the
benchmark's delegating wrapper around the backend it hands to
``FleetEngine``."""


def read(run):
    if not run.calls:
        return None
    return sum(c.surface_s for c in run.calls) / len(run.calls) * 1e3
