"""Lane-events simulated by all calls of the window over its wall time.

A lane's events are ``n_events + burn_in`` per call, counted from the mix
as stated; the reference checks that the lanes simulated them."""


def read(run):
    if not run.calls or run.trace is not None:
        return None
    return run.lane_events / run.window_s
