"""What-if answers returned in the window over its wall time."""


def read(run):
    if not run.calls or run.trace is not None:
        return None
    return len(run.answers) / run.window_s
