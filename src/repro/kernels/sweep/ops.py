"""Public wrapper for the batched-event sweep kernel.

Unlike the attention/SSD ops this entry is not jitted here: ``step`` is a
per-call closure (the engine binds its event body over static descriptors),
so the callers — :mod:`repro.core.engine`'s ``impl="pallas"`` dispatch —
wrap it in their own module-scope jits with the descriptors as static args.

``interpret=None`` auto-selects: compiled Mosaic on TPU backends, the
Pallas interpreter everywhere else (CPU/GPU), so the same call site is
correct on every host and tier-1 stays green without an accelerator.
"""
from __future__ import annotations

import jax

from repro.kernels.sweep.sweep import batched_event_windows


def default_interpret() -> bool:
    """True unless the default backend can compile the kernel (TPU)."""
    return jax.default_backend() != "tpu"


def batched_events(step, state, params, stats_zero, events_per_window, *,
                   slab=None, tile: int = 256, interpret: bool | None = None,
                   epilogue=None):
    """Run stacked event windows on-chip; see ``batched_event_windows``."""
    if interpret is None:
        interpret = default_interpret()
    return batched_event_windows(step, state, params, stats_zero,
                                 events_per_window, slab=slab, tile=tile,
                                 interpret=interpret, epilogue=epilogue)
