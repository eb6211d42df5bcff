"""Device busy time per what-if answer, in ms: the union of the device
ops' intervals in the traced calls, mean over devices, over the
answers.  It holds the kernel and the glue around it (slab keys,
padding, reductions), so work moved onto the device shows here and not
in ``kernel_ns_per_event.whatif``."""


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or not run.answers:
        return None
    return 1e3 * tr.busy_s() / len(tr.calls())
