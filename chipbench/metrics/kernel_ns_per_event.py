"""Device time of the batched-event kernel per simulated lane-event of a
sweep's traced calls (``chipbench.trace.kernel_ns_per_event``)."""
from chipbench.trace import kernel_ns_per_event


def read(run):
    return kernel_ns_per_event(run)
