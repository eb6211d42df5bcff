"""The frozen lowering matrix: the engine's programs, byte for byte.

Shared by ``tools/freeze_hlo_baseline.py`` (which writes
``tests/data/hlo_pr6.json`` from a reference tree) and
``tests/test_env.py`` (which re-lowers the same matrix and compares
sha256 digests byte-for-byte).  Lowered StableHLO text is
compiler-version specific, so the baseline records the jax version and
default backend; the comparison is skipped when either differs — inside
the pinned container (and any matching CI runner) it is exact.

Every entry lowers one of the engine's module-scope jit wrappers with
``env``/``telemetry`` off: if threading the environment-timeline axis
through the engine perturbs even one op in the ``env=None`` program, the
digest moves and the frozen test fails.

The matrix freezes every later statically-absent axis for free: the
``work=`` job-structure axis (PR 10) threads through the same wrappers
as trailing ``work=None, wk=None`` defaults, so these digests — still
compared against the *pre-env* baseline — are simultaneously the
byte-identity proof for ``work=None``.  A new axis that moves even one
op in the off program shows up here as a moved digest.

Beyond the 24 (loop × executor × rng) cells, the matrix holds the
programs the chip and the sharded path run: the three loops'
``shard="lanes"`` programs over a one-device lane mesh (``xla`` and
interpreted ``pallas``), ``run_market_sweep``'s one jitted front at every
executor and with a mesh, and each loop's executors with the telemetry,
env and work axes all on (``*+axes``) — so a rewrite of the executor
layer is held to the same programs, axes on and off.

Symbol names of the engine's own jits, where one program calls another,
are normalized like the entry name: renaming an executor moves no digest.
"""
from __future__ import annotations

import hashlib
import re

import jax
import jax.numpy as jnp

import numpy as np

from repro.core import engine
from repro.core.arrivals import Exponential
from repro.core.env import EnvTimeline, inject_storm
from repro.core.market import NoticeAwareKernel, SpotMarket, SpotPool
from repro.core.policies import ThreePhaseKernel
from repro.core.regions import Region, RegionTopology, RoutingKernel
from repro.core.work import WorkModel
from repro.distributed.sharding import lane_mesh
from repro.obs import Telemetry

_N_EVENTS, _CHUNK, _BURN = 3000, 1024, 512

# jit entry names embed the wrapper's function name; normalize them so a
# pure rename (no program change) cannot masquerade as a lowering change
_NAME = re.compile(r"jit__\w+")
# a jit called from another jit lowers to a private function named after
# it (``@_name``, ``@_name_12`` when repeated)
_SYMBOL = re.compile(r"@(\w+?)(_\d+)?\b")
_JITS = frozenset(name for name, f in vars(engine).items()
                  if callable(f) and hasattr(f, "lower"))


def _nested(m) -> str:
    return "@jit_NESTED" if m.group(1) in _JITS else m.group(0)


def _digest(lowered) -> str:
    text = _SYMBOL.sub(_nested, _NAME.sub("jit_ENTRY", lowered.as_text()))
    return hashlib.sha256(text.encode()).hexdigest()


def _market() -> SpotMarket:
    return SpotMarket(pools=(
        SpotPool(arrival=Exponential(0.9), price=1.0, hazard=0.3, notice=0.1),
        SpotPool(arrival=Exponential(0.5), price=0.6, hazard=0.8, notice=0.3),
    ))


def _topo() -> RegionTopology:
    return RegionTopology(regions=(
        Region(job=Exponential(1.2), spot=Exponential(0.9), price=1.0,
               hazard=0.3, notice=0.1, rmax=4),
        Region(job=Exponential(0.7), spot=Exponential(0.5), price=0.6,
               hazard=0.8, notice=0.3, rmax=4),
    ))


def lowering_digests() -> dict:
    """sha256 of the lowered text for every cell of the matrix."""
    mkern = NoticeAwareKernel(checkpoint_time=0.05)
    market, topo = _market(), _topo()
    mp, rp = market.params(), topo.params()
    # (name prefix, loop, its config tree at one point, across 3 points)
    loops = (
        ("", engine._QueueLoop(Exponential(1.2), Exponential(0.9),
                               ThreePhaseKernel(), 4), None, None),
        ("market_", engine._MarketLoop(Exponential(1.2), market, mkern, 4,
                                       True), mp, _points(mp)),
        ("region_", engine._RegionLoop(
            topo, RoutingKernel(base=mkern, choice="cheapest"), True), rp,
         _points(rp)),
    )
    params = {"r": jnp.float32(2.0)}
    k = jnp.float32(12.0)
    key = jax.random.key(0)
    keys = jax.random.split(key, 2)
    rkeys = jax.random.key_data(keys)
    pflat = {"r": jnp.full((3,), 2.0, jnp.float32)}
    kflat = jnp.full((3,), 12.0, jnp.float32)
    mesh = lane_mesh()

    out = {}
    for name, loop, cfg, cfg_f in loops:
        lanes = (pflat, cfg_f, kflat, rkeys)
        for rng in ("split", "slab"):
            out[f"{name}sim/{rng}"] = _digest(engine._run_sim_jit.lower(
                loop, _N_EVENTS, _CHUNK, 0, rng, params, cfg, k, key))
            out[f"{name}sweep/{rng}"] = _digest(engine._run_sweep_jit.lower(
                loop, _N_EVENTS, _CHUNK, _BURN, rng, pflat, cfg_f, kflat,
                keys))
            for ex in ("pallas", "ref"):
                out[f"{name}sweep_{ex}/{rng}"] = _digest(
                    engine._run_lanes_jit.lower(
                        loop, _N_EVENTS, _CHUNK, _BURN, 2, True, None,
                        *lanes, executor=ex, rng=rng))
            # the shard="lanes" programs over a one-device mesh
            for ex in ("xla", "pallas"):
                out[f"{name}sweep_sharded_{ex}/{rng}"] = _digest(
                    engine._run_lanes_jit.lower(
                        loop, _N_EVENTS, _CHUNK, _BURN, 2, True, mesh,
                        *lanes, executor=ex, rng=rng))
        out.update(_axes_digests(name, loop, cfg, params, k, key, keys,
                                 lanes, mesh))
    out.update(_front_digests(loops[1][1]))
    return out


def _points(cfg: dict) -> dict:
    """A config tree at 3 grid points."""
    return jax.tree.map(lambda a: jnp.broadcast_to(a, (3,) + a.shape), cfg)


def _front_digests(loop) -> dict:
    """``run_market_sweep``'s one program (prep and executor), at the
    arguments the entry point hands it."""
    params = {"r": np.linspace(0.5, 2.0, 3).astype(np.float32)}
    overrides = {"price": None, "hazard": None, "notice": None,
                 "spot_scale": None}
    key, out = jax.random.key(0), {}
    for rng in ("split", "slab"):
        for impl, mesh in (("xla", None), ("pallas", None), ("ref", None),
                           ("pallas", lane_mesh())):
            name = impl if mesh is None else f"sharded_{impl}"
            out[f"market_call_{name}/{rng}"] = _digest(
                engine._market_sweep_call.lower(
                    loop, _N_EVENTS, _CHUNK, _BURN, 2, True, mesh, impl, rng,
                    None, None, (3,), 2, params, loop.market.host_params(),
                    12.0, overrides, key))
    return out


def _axes_digests(name, loop, cfg, params, k, key, keys, lanes,
                  mesh) -> dict:
    """A loop's sim, sweep and lane executors with telemetry, a storm
    timeline and a work model on, on the slab stream."""
    env = inject_storm(EnvTimeline.constant(), 50.0, 150.0)
    work = WorkModel(total_work=2.0, restart_overhead=0.25, ckpt="notice",
                     ckpt_time=0.05)
    ax = dict(tel=Telemetry(), ep=env.params(loop.n_locs), work=work,
              wk=work.params())
    pflat, cfg_f, kflat, _ = lanes
    out = {
        f"{name}sim+axes/slab": _digest(engine._run_sim_jit.lower(
            loop, _N_EVENTS, _CHUNK, _BURN, "slab", params, cfg, k, key,
            **ax)),
        f"{name}sweep+axes/slab": _digest(engine._run_sweep_jit.lower(
            loop, _N_EVENTS, _CHUNK, _BURN, "slab", pflat, cfg_f, kflat,
            keys, **ax)),
        f"{name}sweep_pallas+axes/slab": _digest(engine._run_lanes_jit.lower(
            loop, _N_EVENTS, _CHUNK, _BURN, 2, True, None, *lanes,
            executor="pallas", rng="slab", **ax)),
    }
    if not name:
        out["sweep_sharded_xla+axes/slab"] = _digest(
            engine._run_lanes_jit.lower(
                loop, _N_EVENTS, _CHUNK, _BURN, 2, True, mesh, *lanes,
                executor="xla", rng="slab", **ax))
    return out


def environment_tag() -> dict:
    return {"jax_version": jax.__version__,
            "backend": jax.default_backend()}

if __name__ == "__main__":
    # subprocess entry for tests/test_env.py: lowering must happen in a
    # fresh interpreter because other test modules mutate process-global
    # backend state (XLA_FLAGS device-count overrides) that perturbs
    # lowered text
    import json

    print(json.dumps({"tag": environment_tag(),
                      "digests": lowering_digests()}))
