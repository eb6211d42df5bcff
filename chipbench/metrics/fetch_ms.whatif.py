"""Host time in the program's ``repro.fetch`` phase per what-if answer,
in ms: one ``jax.device_get`` of the whole stats pytree."""
from chipbench.spans import FETCH, phase_ms


def read(run):
    return phase_ms(run, FETCH)
