"""Host time in the program's ``repro.prep`` phase per what-if answer,
in ms: argument checks, array conversion and broadcast, the key split
and the what-if's own grid conversion and kernel."""
from chipbench.spans import PREP, phase_ms


def read(run):
    return phase_ms(run, PREP)
