"""The sweep engine's executors compile for a TPU v5e that is not attached.

The TPU compiler is installed with JAX, so the main path is compiled here
for a described ``v5e:2x2`` topology at the size ``chip_smoke.py`` runs on
the chip: 4,096 lanes (64 r-values x 64 seeds), ``rmax=64``, ``tile=256``,
``chunk_events=65536``.  The compiled Pallas executors (``interpret=False``,
``rng="slab"``) of all three loops must lower to a Mosaic kernel
(``tpu_custom_call``) that the compiler accepts, the region loop also
with its revocation and resume branch on (``region_preempt``); the XLA
executor and the four-chip ``shard="lanes"`` program must compile too.
So must the one program ``SpotCluster.what_if_sweep`` dispatches on a
TPU, argument prep included, at its own 32 lanes, in every variant:
plain, ``telemetry=`` and ``shard="lanes"`` on four chips.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.  The persistent compile cache is off around these compiles (a
compile for a described chip cannot be read back).
"""
import inspect
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core import engine as E
from repro.core.arrivals import Exponential
from repro.core.market import NoticeAwareKernel, SpotMarket, SpotPool
from repro.core.policies import ThreePhaseKernel
from repro.core.regions import Region, RegionTopology, RoutingKernel

LAM, MU = 1 / 12, 1 / 24
G, S = 64, 64  # grid points x seeds = 4,096 lanes
RMAX, TILE, CHUNK = 64, 256, 65_536
N_EVENTS, BURN_IN = 1 << 20, 1 << 14

MARKET = SpotMarket(pools=(
    SpotPool(Exponential(MU / 4), price=0.5, hazard=0.02, notice=0.5),
    SpotPool(Exponential(MU / 4), price=0.3, hazard=0.05, notice=0.01),
    SpotPool(Exponential(MU / 4), price=0.2, hazard=0.0),
    SpotPool(Exponential(MU / 4), price=0.1, hazard=0.10, notice=2.0),
))
ONE_POOL = SpotMarket(pools=(
    SpotPool(Exponential(MU), price=0.3, hazard=0.05, notice=0.5),))
TOPOLOGY = RegionTopology(regions=(
    Region(job=Exponential(1 / 24), spot=Exponential(1 / 48), price=0.9),
    Region(job=Exponential(1 / 24), spot=Exponential(1 / 48), price=0.2),
))
#: The benchmark's two spot regions (``region2_least_loaded``): the
#: Advisor bands' hazards and a two-minute notice, so the region loop's
#: revocation and resume branch is in the kernel.
PREEMPT_TOPOLOGY = RegionTopology(regions=(
    Region(job=Exponential(1 / 24), spot=Exponential(1 / 48), price=1.0,
           hazard=0.0002635231406129536, notice=1 / 30),
    Region(job=Exponential(1 / 24), spot=Exponential(1 / 48), price=2.0,
           hazard=3.46819287456026e-05, notice=1 / 30),
))


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _per_point(tree, sharding, g=G):
    """A config's param arrays with a leading grid axis, as shapes."""
    return jax.tree.map(
        lambda a: _spec(sharding, (g,) + np.shape(a), np.asarray(a).dtype),
        tree)


def _pallas_lowering(loop, sh):
    r, k = {"r": _spec(sh, (G,))}, _spec(sh, (G,))
    keys = _spec(sh, (S, 2), jnp.uint32)
    if loop == "single":
        desc, cfg = E._QueueLoop(Exponential(LAM), Exponential(MU),
                                 ThreePhaseKernel(), RMAX), None
    elif loop in ("market", "one_pool_market"):
        market = MARKET if loop == "market" else ONE_POOL
        desc = E._MarketLoop(Exponential(LAM), market,
                             NoticeAwareKernel(checkpoint_time=0.05), RMAX,
                             market.preemptible)
        cfg = _per_point(market.params(), sh)
    elif loop == "region_preempt":
        kernel = RoutingKernel(NoticeAwareKernel(checkpoint_time=0.025),
                               choice="least_loaded")
        desc = E._RegionLoop(PREEMPT_TOPOLOGY, kernel, True)
        cfg = _per_point(PREEMPT_TOPOLOGY.params(), sh)
    else:
        kernel = RoutingKernel(ThreePhaseKernel(), choice="least_loaded")
        desc = E._RegionLoop(TOPOLOGY, kernel, TOPOLOGY.preemptible)
        cfg = _per_point(TOPOLOGY.params(), sh)
    return E._run_lanes_jit.lower(desc, N_EVENTS, CHUNK, BURN_IN, TILE, False,
                                  None, r, cfg, k, keys, executor="pallas",
                                  rng="slab")


@pytest.mark.parametrize("loop", ["single", "market", "one_pool_market",
                                  "region", "region_preempt"])
def test_pallas_executor_compiles_to_mosaic(loop, one_chip,
                                            no_compile_cache):
    lowered = _pallas_lowering(loop, one_chip)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()
    # no slab in HBM: the kernel's temporaries are small at 4,096 lanes
    assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


def test_xla_executor_compiles(one_chip, no_compile_cache):
    keys = _spec(one_chip, (S,), jax.random.key(0).dtype)
    compiled = E._run_sweep_jit.lower(
        E._QueueLoop(Exponential(LAM), Exponential(MU), ThreePhaseKernel(),
                     RMAX),
        N_EVENTS, CHUNK, BURN_IN, "slab", {"r": _spec(one_chip, (G,))},
        None, _spec(one_chip, (G,)), keys).compile()
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_sharded_pallas_compiles_for_four_chips(topo, no_compile_cache):
    mesh = Mesh(np.array(topo.devices), ("lanes",))
    rep = NamedSharding(mesh, PartitionSpec())
    compiled = E._run_lanes_jit.lower(
        E._QueueLoop(Exponential(LAM), Exponential(MU), ThreePhaseKernel(),
                     RMAX),
        1 << 18, CHUNK, BURN_IN, TILE, False, mesh,
        {"r": _spec(rep, (4 * G,))}, None, _spec(rep, (4 * G,)),
        _spec(rep, (S, 2), jnp.uint32), executor="pallas",
        rng="slab").compile()
    assert "tpu_custom_call" in compiled.as_text()


def _what_if_lowering(variant, topo, monkeypatch):
    """What ``SpotCluster.what_if_sweep`` dispatches on a TPU for 16 r at
    its defaults (2 seeds: 32 lanes; 20,000 events: one window, one tile):
    the engine's one program (``_market_sweep_call``), lowered at the very
    arguments the what-if hands it (``rs`` float32 NumPy, ``k`` a float, a
    typed key, the market's NumPy params) placed on the described chips."""
    import repro.kernels.sweep.ops as ops
    from repro.cluster.orchestrator import (OnlineAdmissionController,
                                            SpotCluster)
    from repro.obs import Telemetry

    class Dispatched(Exception):
        pass

    front, seen = E._market_sweep_call, {}

    def dispatch(*args, **kw):
        seen.update(inspect.signature(front).bind(*args, **kw).arguments)
        raise Dispatched

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    monkeypatch.setattr(E, "_market_sweep_call", dispatch)
    mesh = Mesh(np.array(topo.devices), ("lanes",))
    variant_kw = {"plain": {}, "telemetry": {"telemetry": Telemetry()},
                  "lanes4": {"shard": "lanes", "mesh": mesh}}[variant]
    cluster = SpotCluster(job_process=Exponential(LAM), market=MARKET,
                          k_cost=10.0,
                          controller=OnlineAdmissionController(delta=12.0),
                          checkpoint_hours=0.025)
    with pytest.raises(Dispatched):
        cluster.what_if_sweep(np.linspace(0.5, 8.0, 16, dtype=np.float32),
                              key=jax.random.key(0), **variant_kw)
    assert (seen["impl"], seen["interpret"], seen["rng"]) == (
        "pallas", False, "slab"), variant
    assert (seen["grid_shape"], seen["n_seeds"]) == ((16,), 2), variant
    sh = (NamedSharding(mesh, PartitionSpec()) if variant == "lanes4"
          else SingleDeviceSharding(topo.devices[0]))

    def placed(x):
        aval = jax.typeof(x)
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=sh,
                                    weak_type=aval.weak_type)

    operands = ("params", "mp", "k", "overrides", "key", "ep", "wk")
    return front.lower(**{name: jax.tree.map(placed, v) if name in operands
                          else v for name, v in seen.items()})


@pytest.mark.parametrize("variant", ["plain", "telemetry", "lanes4"])
def test_what_if_compiles_to_the_kernel(variant, topo, no_compile_cache,
                                        monkeypatch):
    compiled = _what_if_lowering(variant, topo, monkeypatch).compile()
    assert "repro_batched_events" in compiled.as_text()


def test_compiled_kernel_refuses_split_stream():
    with pytest.raises(ValueError, match="rng='slab' only"):
        E.run_sweep(Exponential(LAM), Exponential(MU), ThreePhaseKernel(),
                    {"r": 2.0}, n_events=100, key=jax.random.key(0),
                    impl="pallas", interpret=False, rng="split")
