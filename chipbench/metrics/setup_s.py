"""Process start to the end of warm-up: imports, the chip, building the
deployment and one call at the window's exact shapes (a compile, or a
load from the persistent cache)."""


def read(run):
    return run.setup_s
