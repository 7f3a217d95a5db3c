"""validate_p95_ms: the 95th percentile over all calls of the window of
one call's wall time, in milliseconds (linear interpolation between
closest ranks)."""
import numpy as np


def read(run):
    if not run.calls:
        return None
    return float(np.percentile([c.wall for c in run.calls], 95.0)) * 1e3
