"""Host time in the program's ``repro.dispatch`` phase per what-if answer,
in ms: the jitted executor call until it returns."""
from chipbench.spans import DISPATCH, phase_ms


def read(run):
    return phase_ms(run, DISPATCH)
