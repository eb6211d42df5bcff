"""Pure-JAX reference for the batched-event sweep kernel.

Same contract as :func:`repro.kernels.sweep.sweep.batched_event_windows`,
built from the ops the engine's ``lax.scan`` path uses: a ``vmap``-ed event
body inside a ``fori_loop`` per window, windows unrolled in Python.  The
kernel must reproduce this reference **bit-for-bit** — the event body is the
same traced function in both, so any divergence is a kernel layout bug, not
numerics (tests/test_sweep_kernel.py asserts exact equality).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def batched_event_windows_ref(step, state, params, stats_zero,
                              events_per_window, *, slab=None, epilogue=None):
    """Reference: ``(final_state, stats)`` with stats leaves (B, W, ...).

    ``slab`` (optional) matches the kernel's contract: ``(keys, n_cols)``
    with ``(B, n_windows, 2)`` raw uint32 keys; each window's slab is drawn
    whole with ``jax.random.bits`` and the event body takes a fourth
    argument — this event's row.
    """
    b = jax.tree.leaves(state)[0].shape[0]
    vstep = jax.vmap(step)

    def window(state, n_ev, xw):
        zeros = jax.tree.map(
            lambda z: jnp.zeros((b,) + z.shape, z.dtype), stats_zero)

        def event(i, carry):
            st, acc = carry
            if xw is None:
                return vstep(st, acc, params)
            x = jax.lax.dynamic_index_in_dim(xw, i, axis=1, keepdims=False)
            return vstep(st, acc, params, x)

        state, acc = jax.lax.fori_loop(0, n_ev, event, (state, zeros))
        if epilogue is not None:
            state = jax.vmap(epilogue)(state)
        return state, acc

    windows = []
    for w, n_ev in enumerate(events_per_window):
        xw = None
        if slab is not None:
            keys, n_cols = slab
            xw = jax.vmap(lambda k, n=n_ev, c=n_cols: jax.random.bits(
                k, (n, c), jnp.uint32))(keys[:, w])
        state, acc = window(state, n_ev, xw)
        windows.append(acc)
    stats = jax.tree.map(lambda *leaves: jnp.stack(leaves, axis=1), *windows)
    return state, stats
