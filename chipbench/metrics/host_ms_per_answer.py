"""Host time per what-if answer, in ms: an answer's wall time minus the
device busy time within its span (argument prep, dispatch, the transfer
back and ``summarize_market``)."""


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    calls = tr.calls()
    if not calls:
        return None
    wall = sum(e - s for s, e in calls)
    return 1e3 * (wall - tr.busy_within(calls)) / len(calls)
