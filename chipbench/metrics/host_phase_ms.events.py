"""Host time in the program's phases per sweep call, in ms: the
``repro.prep``, ``repro.dispatch``, ``repro.fetch`` and
``repro.summarize`` spans (all but the wait on the device)."""
from chipbench.spans import HOST_PHASES, phase_ms


def read(run):
    return phase_ms(run, *HOST_PHASES)
