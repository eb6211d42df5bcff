"""Mixture-of-Experts with expert parallelism over the ``model`` mesh axis.

Dispatch is **sort-free scatter/gather** (not the classic GShard one-hot
einsum, whose (T, E, C) dispatch tensor is infeasible at 10⁶-token batches):

  1. router top-k → (expert id, gate) per token-slot,
  2. position-in-expert via a cumsum over the one-hot assignment,
  3. scatter tokens into a capacity buffer (E, C, D) — drops overflow,
  4. *expert parallelism*: ``all_to_all`` over the ``model`` axis inside a
     ``shard_map`` region (explicit collective → visible in the roofline),
  5. batched per-expert SwiGLU matmuls (MXU-shaped),
  6. reverse all_to_all, gather + gate-combine.

Two entry points:
  * :func:`moe_apply_local`   — single-device path (smoke tests, oracle).
  * :func:`moe_apply_sharded` — shard_map path used under the production mesh.

Experts are padded to a multiple of the model-axis size (e.g. qwen2-moe's 60
routed experts → 64, the 4 pads masked to −inf in routing) so the expert
dimension shards evenly — standard practice, recorded in DESIGN.md.

The router aux (load-balance) loss is the Switch/GShard form
``E · Σ_e f_e p_e``, psum-averaged over the data axes.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp


def moe_init(key, d_model: int, moe_d_ff: int, num_experts: int,
             num_padded: int, dtype=jnp.bfloat16) -> dict:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(moe_d_ff)
    E = num_padded
    return {
        "router": (jax.random.normal(k1, (d_model, E)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(k2, (E, d_model, moe_d_ff)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k3, (E, d_model, moe_d_ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k4, (E, moe_d_ff, d_model)) * s_out).astype(dtype),
    }


def padded_experts(num_experts: int, model_axis: int) -> int:
    return -(-num_experts // model_axis) * model_axis


def _route(params, x2d, num_real: int, top_k: int):
    """x2d (T, D) -> gates (T,k) f32, ids (T,k) i32, router probs (T,E) f32."""
    logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32), params["router"])
    E = logits.shape[-1]
    if num_real < E:  # mask padded experts out of routing
        pad_mask = jnp.arange(E) >= num_real
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    top_logits, ids = jax.lax.top_k(logits, top_k)
    gates = jax.nn.softmax(top_logits, axis=-1)
    probs = jax.nn.softmax(logits, axis=-1)
    return gates, ids.astype(jnp.int32), probs


def _dispatch_compute_combine(params, x2d, gates, ids, capacity: int):
    """Scatter → batched expert SwiGLU → gather.  Local (per-shard) shapes."""
    T, D = x2d.shape
    k = ids.shape[-1]
    E = params["w_gate"].shape[0]
    flat_ids = ids.reshape(-1)  # (T*k,)
    # position of each (token, slot) within its expert
    onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)  # (T*k, E)
    pos_all = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos_all, flat_ids[:, None], axis=1)[:, 0]
    keep = pos < capacity
    pos_c = jnp.minimum(pos, capacity - 1)

    x_rep = jnp.repeat(x2d, k, axis=0)  # (T*k, D)
    updates = jnp.where(keep[:, None], x_rep, 0)
    buf = jnp.zeros((E, capacity, D), x2d.dtype)
    buf = buf.at[flat_ids, pos_c].add(updates, mode="drop")

    buf = _expert_ffn(params, buf)

    gathered = buf[flat_ids, pos_c]  # (T*k, D)
    gathered = jnp.where(keep[:, None], gathered, 0)
    y = jnp.einsum("tkd,tk->td", gathered.reshape(T, k, D),
                   gates.astype(x2d.dtype))
    return y


def _expert_ffn(params, buf):
    """buf (E, C, D) -> (E, C, D) batched SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", buf, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, params["w_down"])


def _aux_loss(probs, ids, num_real: int, top_k: int):
    """Switch-style load-balance loss on the real experts."""
    E = probs.shape[-1]
    assigned = jax.nn.one_hot(ids.reshape(-1), E, dtype=jnp.float32)
    f = assigned.mean(axis=0) * top_k  # fraction dispatched per expert
    p = probs.mean(axis=0)
    return num_real * jnp.sum(f * p) / top_k


def moe_apply_local(params, x, cfg) -> tuple[jax.Array, jax.Array]:
    """Single-device MoE (oracle / smoke tests).  x (B,S,D)."""
    B, S, D = x.shape
    x2d = x.reshape(B * S, D)
    gates, ids, probs = _route(params, x2d, cfg.num_experts,
                               cfg.num_experts_per_tok)
    T = B * S
    E = params["w_gate"].shape[0]
    capacity = max(
        8, int(math.ceil(T * cfg.num_experts_per_tok * cfg.capacity_factor / E))
    )
    y = _dispatch_compute_combine(params, x2d, gates, ids, capacity)
    aux = _aux_loss(probs, ids, cfg.num_experts, cfg.num_experts_per_tok)
    return y.reshape(B, S, D), aux


def moe_apply_sharded(params, x, cfg, mesh, batch_axes: tuple,
                      model_axis: str = "model") -> tuple[jax.Array, jax.Array]:
    """Expert-parallel MoE under shard_map.  x (B,S,D) sharded over batch."""
    from jax.sharding import PartitionSpec as P

    B, S, D = x.shape
    n_batch_shards = 1
    for a in batch_axes:
        n_batch_shards *= mesh.shape[a]
    n_model = mesh.shape[model_axis]
    E = params["w_gate"].shape[0]
    T_loc = (B * S) // n_batch_shards
    cap_loc = max(
        8,
        int(math.ceil(T_loc * cfg.num_experts_per_tok * cfg.capacity_factor / E)),
    )

    def local_fn(p_local, x_loc):
        """Per-shard: x_loc (T_loc, D); p_local has experts sharded E_loc."""
        gates, ids, probs = _route(
            {**p_local, "router": p_local["router"]}, x_loc,
            cfg.num_experts, cfg.num_experts_per_tok,
        )
        k = cfg.num_experts_per_tok
        flat_ids = ids.reshape(-1)
        onehot = jax.nn.one_hot(flat_ids, E, dtype=jnp.int32)
        pos_all = jnp.cumsum(onehot, axis=0) - 1
        pos = jnp.take_along_axis(pos_all, flat_ids[:, None], axis=1)[:, 0]
        keep = pos < cap_loc
        pos_c = jnp.minimum(pos, cap_loc - 1)
        x_rep = jnp.repeat(x_loc, k, axis=0)
        updates = jnp.where(keep[:, None], x_rep, 0)
        buf = jnp.zeros((E, cap_loc, D), x_loc.dtype)
        buf = buf.at[flat_ids, pos_c].add(updates, mode="drop")

        # expert parallelism: exchange capacity shards for expert shards
        buf = jax.lax.all_to_all(buf, model_axis, split_axis=0, concat_axis=1,
                                 tiled=True)  # (E_loc, cap_loc*n_model, D)
        buf = _expert_ffn(
            {"w_gate": p_local["w_gate"], "w_up": p_local["w_up"],
             "w_down": p_local["w_down"]}, buf)
        buf = jax.lax.all_to_all(buf, model_axis, split_axis=1, concat_axis=0,
                                 tiled=True)  # (E, cap_loc, D)

        gathered = buf[flat_ids, pos_c]
        gathered = jnp.where(keep[:, None], gathered, 0)
        y = jnp.einsum("tkd,tk->td", gathered.reshape(T_loc, k, D),
                       gates.astype(x_loc.dtype))
        aux = _aux_loss(probs, ids, cfg.num_experts, cfg.num_experts_per_tok)
        aux = jax.lax.pmean(aux, batch_axes)
        return y, aux

    param_specs = {
        "router": P(None, None),
        "w_gate": P(model_axis, None, None),
        "w_up": P(model_axis, None, None),
        "w_down": P(model_axis, None, None),
    }
    x2d = x.reshape(B * S, D)
    y2d, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(param_specs, P(batch_axes, None)),
        out_specs=(P(batch_axes, None), P()),
        check_vma=False,
    )(params, x2d)
    return y2d.reshape(B, S, D), aux
