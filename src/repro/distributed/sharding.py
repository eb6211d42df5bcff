"""Logical→physical sharding rules (GSPMD PartitionSpecs by tree path).

Two families live here: the seed's LM-layer GSPMD rules (param / batch /
cache / ZeRO-1 specs below) and the event engine's lane-axis helpers
(:func:`lane_mesh` / :func:`lane_spec` / :func:`pad_lanes` +
:func:`shard_map_1d`), which back the sweep entry points' ``shard="lanes"``
dispatch — the flattened (grid × seeds) lane axis partitioned across a
1-D device mesh (docs/scaling.md).

Axis convention (production mesh, DESIGN.md §5):
  batch        → ("pod", "data")   (DP across pods and within a pod)
  heads / FFN hidden / experts / vocab / d_inner → "model"  (TP / EP)
  everything small (norms, biases of unshardable dims, B/C projections of
  SSD with ngroups=1, routers) → replicated

Divisibility is checked against the actual mesh axis size — e.g. granite's
single KV head or qwen1.5's 20 query heads fall back to replication instead
of producing an invalid spec (recorded per-param, visible in tests).

ZeRO-1 (``zero1_state_specs``): optimizer-state trees additionally shard
their largest still-unsharded divisible axis over "data", reproducing the
ZeRO-1 gather/scatter pattern through GSPMD.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P
from jax.tree_util import DictKey, GetAttrKey, SequenceKey


def shard_map_1d(f, *, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the replication check (the lane bodies
    are per-lane independent; nothing is replicated across shards)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


#: Mesh axis name for the engine's flattened sweep lane axis.
LANE_AXIS = "lanes"


def lane_mesh(devices: int | list | None = None, *,
              axis: str = LANE_AXIS) -> Mesh:
    """1-D device mesh over the sweep engine's flattened lane axis.

    ``devices`` is a device count (the first N local devices), an explicit
    device sequence, or None for every local device.  Simulated host
    devices come from ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
    set before the JAX backend initializes (see docs/scaling.md).
    """
    if devices is None:
        devs = jax.devices()
    elif isinstance(devices, int):
        local = jax.devices()
        if devices < 1 or devices > len(local):
            raise ValueError(
                f"lane_mesh: requested {devices} devices but "
                f"{len(local)} are available (set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=N before the "
                f"backend initializes to simulate more on CPU)")
        devs = local[:devices]
    else:
        devs = list(devices)
    return Mesh(np.array(devs), (axis,))


def lane_spec(mesh: Mesh) -> P:
    """PartitionSpec placing a leading lane axis on ``mesh``'s only axis."""
    if len(mesh.axis_names) != 1:
        raise ValueError(
            f"lane sharding needs a 1-D mesh, got axes {mesh.axis_names}")
    return P(mesh.axis_names[0])


def pad_lanes(tree, n_pad: int):
    """Pad every lane-leading leaf with ``n_pad`` copies of lane 0.

    Lane 0 is a real lane, so the pad lanes run valid simulations (no
    NaN/inf hazards from zero-filled params); the caller slices them off
    after the sharded run.  The lane count becomes divisible by the mesh
    size — the pad half of the sharded sweeps' pad-and-mask contract.
    """
    if n_pad == 0:
        return tree
    return jax.tree.map(
        lambda x: jnp.concatenate(
            [x, jnp.broadcast_to(x[:1], (n_pad,) + x.shape[1:])], axis=0),
        tree)


def _path_names(path) -> list[str]:
    names = []
    for e in path:
        if isinstance(e, DictKey):
            names.append(str(e.key))
        elif isinstance(e, GetAttrKey):
            names.append(e.name)
        elif isinstance(e, SequenceKey):
            names.append(str(e.idx))
    return names


def _with_axis(rank: int, axis: int, name: str) -> P:
    spec = [None] * rank
    spec[axis] = name
    return P(*spec)


def param_specs(params, *, model_axis: str = "model",
                model_size: int, num_heads: int, num_kv_heads: int) -> Any:
    """PartitionSpec tree mirroring a (possibly layer-stacked) param tree."""

    def rule(path, leaf):
        names = _path_names(path)
        last = names[-1]
        rank = len(leaf.shape)
        in_moe = "moe" in names

        def div(n):
            return n % model_size == 0

        if last == "embed":
            return P(model_axis, None) if div(leaf.shape[0]) else P(None, None)
        if last == "lm_head":
            return P(None, model_axis) if div(leaf.shape[1]) else P(None, None)
        if last == "wq":
            return (_with_axis(rank, rank - 2, model_axis)
                    if div(leaf.shape[rank - 2]) else P(*[None] * rank))
        if last in ("wk", "wv"):
            return (_with_axis(rank, rank - 2, model_axis)
                    if div(leaf.shape[rank - 2]) else P(*[None] * rank))
        if last == "wo":
            return (_with_axis(rank, rank - 3, model_axis)
                    if div(leaf.shape[rank - 3]) else P(*[None] * rank))
        if last in ("bq", "bk", "bv"):
            return (_with_axis(rank, rank - 2, model_axis)
                    if div(leaf.shape[rank - 2]) else P(*[None] * rank))
        if last in ("w_gate", "w_up"):
            if in_moe:  # (..., E, D, F): expert-parallel
                return (_with_axis(rank, rank - 3, model_axis)
                        if div(leaf.shape[rank - 3]) else P(*[None] * rank))
            return (_with_axis(rank, rank - 1, model_axis)
                    if div(leaf.shape[rank - 1]) else P(*[None] * rank))
        if last == "w_down":
            if in_moe:  # (..., E, F, D)
                return (_with_axis(rank, rank - 3, model_axis)
                        if div(leaf.shape[rank - 3]) else P(*[None] * rank))
            return (_with_axis(rank, rank - 2, model_axis)
                    if div(leaf.shape[rank - 2]) else P(*[None] * rank))
        if last in ("z_proj", "x_proj", "dt_proj"):
            return (_with_axis(rank, rank - 1, model_axis)
                    if div(leaf.shape[rank - 1]) else P(*[None] * rank))
        if last in ("conv_x_w", "conv_x_b"):
            return (_with_axis(rank, rank - 1, model_axis)
                    if div(leaf.shape[rank - 1]) else P(*[None] * rank))
        if last == "out_proj":
            return (_with_axis(rank, rank - 2, model_axis)
                    if div(leaf.shape[rank - 2]) else P(*[None] * rank))
        # router, b_proj/c_proj, conv_bc_*, norms, A_log/D/dt_bias, scales
        return P(*[None] * rank)

    return jax.tree_util.tree_map_with_path(rule, params)


def batch_specs(batch, batch_axes: tuple) -> Any:
    """Input-batch specs: shard the batch dim; positions lead with axis 3."""

    def rule(path, leaf):
        names = _path_names(path)
        rank = len(leaf.shape)
        if names[-1] == "positions":  # (3, B, S)
            return P(None, batch_axes, *([None] * (rank - 2)))
        return P(batch_axes, *([None] * (rank - 1)))

    return jax.tree_util.tree_map_with_path(rule, batch)


def cache_specs(cache, *, batch_axes: tuple, model_axis: str = "model",
                model_size: int, shard_kv_seq: bool = False) -> Any:
    """Decode-cache specs.  Layer-stacked KV: (L, B, S, KH, hd)."""

    def rule(path, leaf):
        names = _path_names(path)
        last = names[-1]
        rank = len(leaf.shape)
        if last == "index":
            return P()
        if last in ("k", "v", "attn_k", "attn_v", "self_k", "self_v",
                    "cross_k", "cross_v"):
            kh, hd = leaf.shape[3], leaf.shape[4]
            if kh % model_size == 0:
                return P(None, batch_axes, None, model_axis, None)
            if hd % model_size == 0:
                # GQA-narrow archs (granite kv=1, qwen3 kv=8, ...): shard the
                # head_dim — contractions over hd become partial-sum +
                # all-reduce, and every seq slice/update stays shard-local
                # (seq-sharding makes GSPMD gather the cache per KV chunk).
                return P(None, batch_axes, None, None, model_axis)
            if shard_kv_seq and leaf.shape[2] % model_size == 0:
                return P(None, batch_axes, model_axis, None, None)
            return P(None, batch_axes, None, None, None)
        if last == "conv":  # (L, B, W-1, C)
            return P(None, batch_axes, None, None)
        if last == "state":  # (L, B, H, P, N)
            h = leaf.shape[2]
            head = model_axis if h % model_size == 0 else None
            return P(None, batch_axes, head, None, None)
        return P(*[None] * rank)

    return jax.tree_util.tree_map_with_path(rule, cache)


def zero1_state_specs(param_spec_tree, params, *, data_axes: tuple,
                      data_size: int) -> Any:
    """Add "data" sharding to the largest unsharded divisible axis."""

    def rule(spec, leaf):
        dims = list(spec) + [None] * (len(leaf.shape) - len(spec))
        # already data-sharded (e.g. params that went through fsdp)
        used = set()
        for d in dims:
            if d is None:
                continue
            for a in (d if isinstance(d, tuple) else (d,)):
                used.add(a)
        if any(a in used for a in data_axes):
            return P(*dims)
        best, best_size = None, 0
        for i, (d, s) in enumerate(zip(dims, leaf.shape)):
            if d is None and s % data_size == 0 and s >= data_size \
                    and s > best_size:
                best, best_size = i, s
        if best is None:
            return P(*dims)
        dims[best] = data_axes if len(data_axes) > 1 else data_axes[0]
        return P(*dims)

    return jax.tree_util.tree_map(rule, param_spec_tree, params,
                                  is_leaf=lambda x: isinstance(x, P))
