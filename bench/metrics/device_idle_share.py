"""device_idle_share: the share of the traced window in which no
operation ran on the device (1 - union of the device-op intervals over
the window), in percent; 100 where no operation ran."""
from bench import profile


def read(run):
    if run.trace is None:
        return None
    return (1.0 - profile.busy_ns(run.trace)
            / profile.window_ns(run.trace)) * 100.0
