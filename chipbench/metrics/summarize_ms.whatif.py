"""Host time in the program's ``repro.summarize`` phase per what-if answer,
in ms: the float64 ``summarize_market`` and the reshape into the grid."""
from chipbench.spans import SUMMARIZE, phase_ms


def read(run):
    return phase_ms(run, SUMMARIZE)
