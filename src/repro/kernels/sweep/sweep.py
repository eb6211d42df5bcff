"""Pallas batched-event kernel for the (grid × slot) sweep hot loop.

The engine's event loop (:mod:`repro.core.engine`) is scalar control flow
over small per-lane state: a handful of clocks plus (rmax,) slot arrays.
Under the XLA ``vmap``-of-``scan`` schedule every one of the N width-``rmax``
selects in the event body is a separate HLO op whose operands round-trip
through HBM once per event.  This kernel flips the layout: a *tile* of
simulation lanes is laid out lane-last — (rmax, tile) slot planes and
(1, tile) per-lane scalars, lanes on the 128-wide minor axis — resident in
VMEM, and a whole float32 window of events (the chunk the engine already
uses for precision) runs as ONE fused kernel body: clock min/argmin merge,
FIFO-oldest/first-free slot reductions (across sublanes), and the one-hot
join/leave updates all stay on-chip for the entire event block.

Tiling: ``grid = (n_tiles, n_windows)`` with the window axis innermost.  The
final-state *output* blocks have an index map that ignores the window axis,
so each lane tile's state block stays resident in VMEM across all of its
windows (the same revisiting schedule as the flash-attention accumulators,
with the out refs themselves as the resident storage): window 0 seeds the
state block from the initial-state inputs, every window reads/writes it
in place, and it is flushed to HBM once per lane tile.  Per-window event
counts arrive as a scalar-prefetched i32 vector in SMEM (one entry per
window — burn-in, full chunks, tail), so burn-in and the remainder window
run through the same kernel body.  Stats leave window-major and
lane-last, one (…, tile) block per (window, tile).

Randomness (``rng="slab"``): no slab is materialized in HBM or VMEM.  Each
window's (2, tile) raw threefry keys stream in as one block and every
event hashes its own (n_cols, tile) row in-kernel (:func:`slab_row`) —
bitwise the row the scan executor reads from its whole-window slab.  The
split stream's per-event ``jax.random.split`` has no Mosaic lowering, so
the compiled kernel runs the slab stream only; the interpreter runs both.

Genericity: the kernel is parameterized by a per-lane ``step(state, stats,
params) -> (state, stats)`` event body and arbitrary state/params/stats
pytrees, so the single-pool engine, the spot-market engine (per-pool
clock vectors, per-pool stat counters), and the multi-region engine
(state blocks grown a region axis, packed slot partitions) share this one
kernel family with zero kernel-side changes — and so do the optional
state/stat extensions that pair onto the carry (the ``env=`` timeline
cursor, the ``work=`` per-slot work structure with its survival-ledger
block).  The body is mapped across the tile inside the kernel by
:func:`repro.kernels.sweep.lanes.lane_map` (``vmap`` with the lane axis
kept last), which keeps each lane's arithmetic op for op the per-lane
program's — bit-for-bit the ``lax.scan`` reference path (see ref.py and
tests/test_sweep_kernel.py).

``interpret=True`` runs the same kernel body through the Pallas
interpreter (tier-1 on CPU hosts); ``interpret=False`` compiles it with
Mosaic for a TPU (tests/test_tpu_compile.py compiles it for a described
v5e, ``chip_smoke.py`` runs it on one).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.sweep.lanes import lane_map

#: Lanes are every block's minor axis, which Mosaic takes only whole or
#: in multiples of 128.
LANE_ALIGN = 128


_THREEFRY_ROT = ((13, 15, 26, 6), (17, 29, 16, 24))


def threefry2x32(k1, k2, x1, x2):
    """JAX's Threefry-2x32 hash (its 20-round unrolled lowering) in plain
    uint32 arithmetic, so a Pallas kernel can evaluate it; arguments
    broadcast against each other.  Returns the two output words."""
    ks = (k1, k2, k1 ^ k2 ^ np.uint32(0x1BD11BDA))
    x = [x1 + ks[0], x2 + ks[1]]
    for i in range(5):
        for r in _THREEFRY_ROT[i % 2]:
            x[0] = x[0] + x[1]
            x[1] = ((x[1] << np.uint32(r)) | (x[1] >> np.uint32(32 - r)))
            x[1] = x[0] ^ x[1]
        x[0] = x[0] + ks[(i + 1) % 3]
        x[1] = x[1] + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x[0], x[1]


def slab_row(key: jax.Array, i, n_cols: int) -> jax.Array:
    """Row ``i`` of ``jax.random.bits(key, (n, n_cols), uint32)`` for any
    ``n > i``, without drawing the other rows, lane-last.

    ``key`` is ``(2, lanes)`` raw words; the row is ``(n_cols, lanes)``.
    Under the default partitionable threefry, the element at flat index
    ``j`` is the xor of the two hash words of the counter ``(0, j)``; row
    ``i`` holds ``j = i * n_cols + c``.
    """
    if not jax.config.jax_threefry_partitionable:
        raise NotImplementedError(
            "slab_row reproduces the partitionable threefry stream; "
            "jax_threefry_partitionable is off")
    j = (jnp.asarray(i, jnp.uint32) * np.uint32(n_cols)
         + jax.lax.broadcasted_iota(jnp.uint32, (n_cols, 1), 0))
    b1, b2 = threefry2x32(key[0:1], key[1:2], np.uint32(0), j)
    return b1 ^ b2


def _lanes_last(x):
    """``(B, *s)`` -> ``(*s, B)``, a per-lane scalar as ``(1, B)``: XLA
    and Mosaic tile a rank-1 array differently, so no block is rank 1."""
    x = jnp.moveaxis(x, 0, -1)
    return x[None] if x.ndim == 1 else x


def _lanes_first(x, shape: tuple):
    """Inverse of :func:`_lanes_last` for per-lane ``shape``."""
    with jax.named_scope("repro.glue.lanes_first"):
        return jnp.moveaxis(x.reshape(shape + x.shape[-1:]), -1, 0)


def _resident_spec(shape: tuple, tile: int) -> pl.BlockSpec:
    """(*rest, tile) block at lane-tile ``t``, resident across windows."""
    rest = tuple(shape[:-1])
    return pl.BlockSpec(rest + (tile,),
                        lambda t, w, nev, _n=len(rest): (0,) * _n + (t,))


def _window_spec(shape: tuple, tile: int) -> pl.BlockSpec:
    """(*rest, tile) block of a window-major ``(n_windows, *rest, B)``
    array at (window ``w``, lane-tile ``t``)."""
    rest = tuple(shape[1:-1])
    return pl.BlockSpec(
        (None,) + rest + (tile,),
        lambda t, w, nev, _n=len(rest): (w,) + (0,) * _n + (t,))


def _window_struct(n_windows: int, lanes: int, z) -> jax.ShapeDtypeStruct:
    """Window-major, lane-last stats output for per-lane accumulator
    ``z``.  Mosaic tiles a block's last two dims, so a per-lane scalar
    gets a unit axis rather than a (window, lane) pair it cannot block by
    one window."""
    return jax.ShapeDtypeStruct((n_windows,) + (z.shape or (1,)) + (lanes,),
                                z.dtype)


def _to_carry(tree):
    """The event loop's carry: bool leaves as int32 (Mosaic carries no i1
    masks through a loop) and per-lane scalars as ``(1, tile)`` rows (a
    rank-1 loop value whose update is a select on a reduced mask fails
    Mosaic's layout inference)."""
    def leaf(x):
        x = x.astype(jnp.int32) if x.dtype == jnp.bool_ else x
        return x[None] if x.ndim == 1 else x
    return [leaf(x) for x in jax.tree.leaves(tree)]


def _from_carry(leaves, like):
    """Inverse of :func:`_to_carry`, shaped and typed as pytree ``like``."""
    ref, treedef = jax.tree.flatten(like)
    return jax.tree.unflatten(treedef, [
        x.reshape(r.shape).astype(r.dtype) for x, r in zip(leaves, ref)])


def _sweep_kernel(nev_ref, *refs, step, epilogue, n_cols, state_tree,
                  state_shapes, params_tree, params_shapes, stats_zero,
                  tile):
    """One (lane-tile, window) grid step: a full event block, fused.

    Every block is lane-last (:mod:`repro.kernels.sweep.lanes`).  nev_ref
    (n_windows,) i32 in SMEM — events per window; refs order is
    [state_in..., params..., slab_key?] then [state_out..., stats_out...].
    state_out doubles as the VMEM-resident engine state across the window
    axis.  With ``n_cols``, the slab key block is this window's (2, tile)
    raw threefry keys and each event's (n_cols, tile) slab row is hashed
    in-kernel (:func:`slab_row`) — bitwise the row the scan executor reads
    from its materialized window slab.
    """
    wj = pl.program_id(1)
    n_state, n_params = len(state_shapes), len(params_shapes)
    state_in = refs[:n_state]
    params_in = refs[n_state:n_state + n_params]
    n_in = n_state + n_params + (1 if n_cols else 0)
    state_out = refs[n_in:n_in + n_state]
    stats_out = refs[n_in + n_state:]

    @pl.when(wj == 0)
    def _seed():
        for dst, src in zip(state_out, state_in):
            dst[...] = src[...]

    def load(refs, shapes):
        return [r[...].reshape(s + (tile,)) for r, s in zip(refs, shapes)]

    state = jax.tree.unflatten(state_tree, load(state_out, state_shapes))
    params = jax.tree.unflatten(params_tree, load(params_in, params_shapes))
    # fresh float32/int32 window accumulators, re-zeroed every window — the
    # engine's chunked-precision scheme, unchanged
    stats = jax.tree.map(lambda z: jnp.zeros(z.shape + (tile,), z.dtype),
                         stats_zero)

    slab_key = refs[n_state + n_params][...] if n_cols else None

    like = jax.eval_shape(lambda: (state, stats))

    def event(i, carry):
        st, acc = _from_carry(carry, like)
        x = () if slab_key is None else (slab_row(slab_key, i, n_cols),)
        return _to_carry(lane_map(step, st, acc, params, *x))

    state, stats = _from_carry(jax.lax.fori_loop(
        0, nev_ref[wj], event, _to_carry((state, stats))), like)
    if epilogue is not None:
        state = lane_map(epilogue, state)
    for dst, leaf in zip(state_out, jax.tree.leaves(state)):
        dst[...] = leaf.reshape(dst.shape)
    for dst, leaf in zip(stats_out, jax.tree.leaves(stats)):
        dst[...] = leaf.reshape(dst.shape)


def check_tile(tile: int, lanes: int, interpret: bool) -> int:
    """Lanes per kernel instance for ``lanes`` lanes: ``tile`` clamped to
    ``lanes``.  Compiled (``interpret=False``), several tiles must each be
    a multiple of :data:`LANE_ALIGN` — lanes are every block's minor axis
    — so any other tile raises."""
    tile = max(1, min(tile, lanes))
    if not interpret and tile < lanes and tile % LANE_ALIGN:
        raise ValueError(
            f"compiled sweep kernel: tile={tile} over {lanes} lanes must be "
            f"a multiple of {LANE_ALIGN} (or cover every lane)")
    return tile


def batched_event_windows(step, state, params, stats_zero, events_per_window,
                          *, slab=None, tile: int = 256,
                          interpret: bool = True, epilogue=None):
    """Run stacked event windows for a batch of simulation lanes on-chip.

    Args:
      step: per-lane event body ``(state, stats, params) -> (state, stats)``
        over unbatched pytrees (mapped across the lane tile in-kernel by
        :func:`repro.kernels.sweep.lanes.lane_map`);
        with ``slab``, the body takes a fourth argument — this event's
        uint32 slab row.
      state: pytree of ``(B, ...)`` arrays — per-lane initial engine state.
      params: pytree of ``(B, ...)`` arrays — per-lane traced parameters.
      stats_zero: pytree of *unbatched* zero accumulators defining the
        per-window stats shapes/dtypes (e.g. ``WindowStats.zeros()``).
      events_per_window: static-length sequence of per-window event counts.
      slab: optional ``(keys, n_cols)``: ``(B, n_windows, 2)`` raw uint32
        keys (:func:`repro.core.clocks.lane_slab_keys`) and a static row
        width.  Event ``i`` of window ``w`` reads row ``i`` of
        ``jax.random.bits(keys[:, w], (n_ev_w, n_cols), uint32)``, hashed
        in-kernel — the engine's slab PRNG stream with no slab in HBM or
        VMEM.
      tile: lanes per kernel instance (see :func:`check_tile`; B is padded
        up to a tile multiple with copies of lane 0, sliced off on return).
      interpret: run through the Pallas interpreter (the CPU path).
      epilogue: optional per-lane ``state -> state`` applied after each
        window (the engine's order-rebase hook).

    Returns ``(final_state, stats)`` where stats leaves are shaped
    ``(B, n_windows, ...)`` — one float32 window of sums per entry of
    ``events_per_window``, assembled in float64 downstream.
    """
    state_leaves, state_tree = jax.tree.flatten(state)
    params_leaves, params_tree = jax.tree.flatten(params)
    b = state_leaves[0].shape[0]
    tile = check_tile(tile, b, interpret)
    pad = -b % tile
    key_leaves, n_cols = ([], 0) if slab is None else ([slab[0]], slab[1])
    if pad:
        def padlane(x):
            fill = jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])
            return jnp.concatenate([x, fill])

        with jax.named_scope("repro.glue.pad_lanes"):
            state_leaves = [padlane(x) for x in state_leaves]
            params_leaves = [padlane(x) for x in params_leaves]
            key_leaves = [padlane(x) for x in key_leaves]
    bp = b + pad
    n_windows = len(events_per_window)
    nev = jnp.asarray(events_per_window, jnp.int32)
    state_shapes = [x.shape[1:] for x in state_leaves]
    state_leaves = [_lanes_last(x) for x in state_leaves]
    params_shapes = [x.shape[1:] for x in params_leaves]
    params_leaves = [_lanes_last(x) for x in params_leaves]
    # window-major keys: each window's (2, tile) block is one squeezed slice
    key_leaves = [jnp.transpose(k, (1, 2, 0)) for k in key_leaves]

    stats_leaves = jax.tree.leaves(stats_zero)
    state_structs = [jax.ShapeDtypeStruct(x.shape, x.dtype)
                     for x in state_leaves]
    stats_structs = [_window_struct(n_windows, bp, z) for z in stats_leaves]
    kernel = functools.partial(
        _sweep_kernel, step=step, epilogue=epilogue,
        n_cols=n_cols, state_tree=state_tree, state_shapes=state_shapes,
        params_tree=params_tree, params_shapes=params_shapes,
        stats_zero=stats_zero, tile=tile,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bp // tile, n_windows),
            in_specs=[_resident_spec(x.shape, tile) for x in state_leaves]
            + [_resident_spec(x.shape, tile) for x in params_leaves]
            + [_window_spec(k.shape, tile) for k in key_leaves],
            out_specs=[_resident_spec(s.shape, tile) for s in state_structs]
            + [_window_spec(s.shape, tile) for s in stats_structs],
        ),
        out_shape=state_structs + stats_structs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="repro_batched_events",
    )(nev, *state_leaves, *params_leaves, *key_leaves)
    n_state = len(state_leaves)
    final_state = jax.tree.unflatten(
        state_tree, [_lanes_first(x, s)[:b]
                     for x, s in zip(out[:n_state], state_shapes)])
    _, stats_tree = jax.tree.flatten(stats_zero)
    stats = jax.tree.unflatten(stats_tree, [
        _lanes_first(x, (n_windows,) + z.shape)[:b]
        for x, z in zip(out[n_state:], stats_leaves)])
    return final_state, stats
