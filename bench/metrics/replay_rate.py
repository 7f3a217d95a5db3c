"""replay_rate: instances validated per second, over the whole window:
the sum of C x N over all calls, divided by the window's seconds, from
the first call's start to the last call's end (drawing inputs and
recording sampled answers between calls included)."""


def read(run):
    if not run.calls:
        return None
    span = run.calls[-1].end - run.calls[0].start
    if span <= 0.0:
        return None
    return sum(c.instances for c in run.calls) / span
