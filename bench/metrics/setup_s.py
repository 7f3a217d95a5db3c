"""setup_s: process start to the first timed call (imports, device
initialisation, the template, one warm-up call at the cell's shapes,
compiling or loading from the compile cache)."""


def read(run):
    return run.setup_s
