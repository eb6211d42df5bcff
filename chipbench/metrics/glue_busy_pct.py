"""Share of device busy time outside the batched-event kernel: the
executor's glue (key streams, padding, gathers, reshapes), in %."""
from chipbench.trace import KERNEL_CATEGORY


def read(run):
    tr = run.trace
    if tr is None or not tr.ops:
        return None
    busy = tr.busy_s()
    kernel = tr.busy_s(lambda n, c: c == KERNEL_CATEGORY)
    if busy <= 0 or kernel <= 0:
        return None
    return 100.0 * (busy - kernel) / busy
