"""Lane-last batching for the batched-event kernel.

``jax.vmap`` puts the batch axis first: a lane tile's per-lane scalars
become ``(tile,)`` vectors and its ``(rmax,)`` slot arrays ``(tile, rmax)``,
so every slot reduction ends on the 128-wide minor axis and every
broadcast back to the slots has to turn a lane vector into a column —
relayouts Mosaic refuses (``tpu.reshape`` of ``vector<256xi1>`` to
``vector<256x1xi1>``).  :func:`lane_map` runs the same per-lane function
with the lane axis LAST instead: slot arrays are ``(rmax, tile)``, slot
reductions run across sublanes, and a per-lane scalar broadcasts down the
sublanes of its own lane.

It traces the per-lane function to a jaxpr once and evaluates it with
every lane-dependent value carrying a trailing lane axis.  Because the
lane axis is last, every axis a per-lane primitive names (a reduction's
``axes``, a cumsum's ``axis``, a concatenate's ``dimension``) still names
the same data, and elementwise primitives apply unchanged; only the
primitives whose parameters spell out a whole shape need a rule.  Each
lane's arithmetic is the per-lane program's, op for op, so the kernel
keeps the reference's bit-for-bit contract.  A primitive without a rule
raises rather than guessing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.extend import core as jcore

# call-like primitives whose body is evaluated inline, and the parameter
# that holds it
_CALL_PARAMS = {"jit": "jaxpr", "pjit": "jaxpr", "closed_call": "call_jaxpr",
                "core_call": "call_jaxpr", "custom_jvp_call": "call_jaxpr",
                "custom_vjp_call": "call_jaxpr", "checkpoint": "jaxpr",
                "remat": "jaxpr"}


def _lift(x, batched: bool, shape: tuple, lanes: int):
    """``x`` as a lane-last ``shape + (lanes,)`` array.  An unbatched
    operand is ``shape`` or a scalar; a batched one is ``shape`` or a
    per-lane scalar (an elementwise primitive's scalar operand)."""
    x = jnp.asarray(x)
    per_lane = x.shape[:-1] if batched else x.shape
    if batched and per_lane == tuple(shape):
        return x
    n = len(shape)
    dims = (n,) if batched else tuple(range(n - x.ndim, n))
    return lax.broadcast_in_dim(x, tuple(shape) + (lanes,), dims)


def _dynamic_slice(ins, params, lanes):
    """``dynamic_slice``: a rank-1 operand selects among its static
    candidate slices (the primitive's start clamping kept), since Mosaic
    lowers no ``dynamic_slice`` of a value; unbatched starts into a
    higher-rank operand slice every lane alike."""
    (x, xb), *starts = ins
    sizes = tuple(params["slice_sizes"])
    shape = x.shape[:-1] if xb else x.shape
    if not any(b for _, b in starts) and len(shape) != 1:
        return [lax.dynamic_slice(_lift(x, xb, shape, lanes),
                                  [s for s, _ in starts] + [0],
                                  sizes + (lanes,))]
    if len(shape) != 1:
        raise NotImplementedError(
            "lane_map: dynamic_slice with a per-lane start needs a rank-1 "
            f"operand, got per-lane shape {shape}")
    x = _lift(x, xb, shape, lanes)
    (start, sb), = starts
    n, size = shape[0], sizes[0]
    start = jnp.clip(_lift(start, sb, (), lanes), 0, n - size)
    out = lax.slice_in_dim(x, 0, size, axis=0)
    for s in range(1, n - size + 1):
        out = jnp.where(start == s, lax.slice_in_dim(x, s, s + size, axis=0),
                        out)
    return [out]


def _rule(eqn, ins, lanes):
    """Lane-last outputs of one equation with at least one batched input."""
    prim, params = eqn.primitive, eqn.params
    name = prim.name
    if name == "broadcast_in_dim":
        (x, _), = ins
        shape = tuple(params["shape"])
        return [lax.broadcast_in_dim(
            x, shape + (lanes,),
            tuple(params["broadcast_dimensions"]) + (len(shape),))]
    if name == "reshape":
        if params.get("dimensions") is not None:
            raise NotImplementedError("lane_map: reshape with dimensions")
        (x, _), = ins
        return [lax.reshape(x, tuple(params["new_sizes"]) + (lanes,))]
    if name == "slice":
        (x, _), = ins
        strides = params["strides"]
        return [lax.slice(x, tuple(params["start_indices"]) + (0,),
                          tuple(params["limit_indices"]) + (lanes,),
                          None if strides is None
                          else tuple(strides) + (1,))]
    if name == "transpose":
        (x, _), = ins
        perm = tuple(params["permutation"])
        return [lax.transpose(x, perm + (len(perm),))]
    if name == "dynamic_slice":
        return _dynamic_slice(ins, params, lanes)
    # typed PRNG keys (the split stream, interpreter only): key data and
    # drawn shapes sit inside or after the key axes, so the lane axis moves
    if name == "random_wrap":
        (x, _), = ins
        return [prim.bind(jnp.moveaxis(x, -1, -2), **params)]
    if name == "random_unwrap":
        (x, _), = ins
        return [jnp.moveaxis(prim.bind(x, **params), -2, -1)]
    if name in ("random_split", "random_bits"):
        (x, _), = ins
        return [jnp.moveaxis(prim.bind(x, **params), x.ndim - 1, -1)]
    if name in ("iota", "pad", "gather", "scatter", "dot_general", "while",
                "cond", "scan", "dynamic_update_slice", "broadcast"):
        raise NotImplementedError(f"lane_map: no lane-last rule for {name}")
    out_shape = tuple(eqn.outvars[0].aval.shape)
    shapes = [tuple(v.aval.shape) for v in eqn.invars]
    elementwise = (all(s in ((), out_shape) for s in shapes)
                   and all(tuple(v.aval.shape) == out_shape
                           for v in eqn.outvars))
    args = [_lift(x, b, out_shape if elementwise else s, lanes)
            for (x, b), s in zip(ins, shapes)]
    subfuns, bind_params = prim.get_bind_params(params)
    out = prim.bind(*subfuns, *args, **bind_params)
    return list(out) if prim.multiple_results else [out]


def _call(eqn, ins, lanes):
    """A call primitive's body, evaluated lane-last.  A ``jit`` stays a
    ``jit`` — XLA's CPU code for some samplers (the gamma rejection loop)
    rounds differently once inlined into its caller."""
    inner = eqn.params[_CALL_PARAMS[eqn.primitive.name]]
    jaxpr = getattr(inner, "jaxpr", inner)
    consts = getattr(inner, "consts", [])
    flags = [b for _, b in ins]
    box = []

    def body(*xs):
        outs = _eval(jaxpr, consts, list(zip(xs, flags)), lanes)
        box.append([b for _, b in outs])
        return [o for o, _ in outs]

    if eqn.primitive.name in ("jit", "pjit"):
        vals = jax.jit(body)(*[x for x, _ in ins])
    else:
        vals = body(*[x for x, _ in ins])
    return list(zip(vals, box[0]))


def _live_eqns(jaxpr):
    """The equations ``jaxpr``'s outputs depend on.  Event bodies compute
    values no output reads — e.g. a zero-width uniform span handed to a
    deterministic choice rule — and Mosaic has no zero-size vectors."""
    live = {v for v in jaxpr.outvars if not isinstance(v, jcore.Literal)}
    keep = []
    for eqn in reversed(jaxpr.eqns):
        if eqn.effects or any(v in live for v in eqn.outvars):
            keep.append(eqn)
            live.update(v for v in eqn.invars
                        if not isinstance(v, jcore.Literal))
    return keep[::-1]


def _eval(jaxpr, consts, args, lanes):
    """Evaluate ``jaxpr`` on ``(value, batched)`` pairs; returns pairs."""
    env = {}

    def read(v):
        if isinstance(v, jcore.Literal):
            return v.val, False
        return env[v]

    for v, c in zip(jaxpr.constvars, consts):
        env[v] = (c, False)
    for v, a in zip(jaxpr.invars, args):
        env[v] = a
    for eqn in _live_eqns(jaxpr):
        ins = [read(v) for v in eqn.invars]
        name = eqn.primitive.name
        if any(b for _, b in ins) and name in _CALL_PARAMS:
            outs = _call(eqn, ins, lanes)
        elif any(b for _, b in ins):
            outs = [(o, True) for o in _rule(eqn, ins, lanes)]
        else:
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            outs = eqn.primitive.bind(*subfuns, *[x for x, _ in ins],
                                      **bind_params)
            outs = outs if eqn.primitive.multiple_results else [outs]
            outs = [(o, False) for o in outs]
        for v, o in zip(eqn.outvars, outs):
            env[v] = o
    return [read(v) for v in jaxpr.outvars]


def lane_map(fun, *args):
    """Apply per-lane ``fun`` to pytrees of lane-last ``(..., lanes)``
    arrays; returns its outputs lane-last.  ``jax.vmap(fun, in_axes=-1,
    out_axes=-1)`` in meaning, with the lane axis kept last throughout."""
    leaves, tree = jax.tree.flatten(args)
    lanes = leaves[0].shape[-1]
    avals = [jax.ShapeDtypeStruct(x.shape[:-1], x.dtype) for x in leaves]
    closed, out_shapes = jax.make_jaxpr(
        lambda *xs: fun(*jax.tree.unflatten(tree, xs)),
        return_shape=True)(*avals)
    outs = _eval(closed.jaxpr, closed.consts, [(x, True) for x in leaves],
                 lanes)
    flat_shapes, out_tree = jax.tree.flatten(out_shapes)
    return jax.tree.unflatten(out_tree, [
        _lift(o, b, s.shape, lanes).astype(s.dtype)
        for (o, b), s in zip(outs, flat_shapes)])
