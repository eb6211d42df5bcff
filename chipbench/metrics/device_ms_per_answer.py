"""Device busy time per what-if answer, in ms: the XLA executor's scan
and whatever else the answer ran on the device."""


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or not run.answers:
        return None
    return 1e3 * tr.busy_s() / len(tr.calls())
