"""Share of the traced window in which no op ran on the device, in %:
1 minus the union of busy intervals over the window, the mean over the
devices the cell uses."""


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    return run.trace.idle_pct()
