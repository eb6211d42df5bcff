"""Device time of the batched-event kernel per simulated lane-event.

The kernel's op time, summed over the devices, over the lane-events of
the traced calls (``n_events + burn_in`` per lane and call).  The kernel
is the op of the Mosaic custom-call category."""
from chipbench.trace import KERNEL_CATEGORY


def is_kernel(name, category):
    return category == KERNEL_CATEGORY


def read(run):
    tr = run.trace
    if tr is None or not tr.ops or not run.calls:
        return None
    kernel_s = tr.busy_s(is_kernel) * len(tr.ops)
    if kernel_s <= 0:
        return None
    return kernel_s * 1e9 / run.lane_events
