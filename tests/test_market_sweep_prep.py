"""``run_market_sweep`` dispatches one program per call.

Its argument prep (the float32 casts, the grid broadcast and flattening,
the pools-config overrides and the seed-key split) runs inside the same
jitted program as the executor.  Checked here, on the CPU:

* every output leaf is bitwise the answer of the same call built the
  eager way (prep one small program at a time, then the jitted executor),
  under ``impl="xla"``, ``"ref"`` and ``"pallas"`` (interpreted), at the
  what-if's own shape, with a per-grid-point ``prices`` override and a
  scalar ``hazards``, with one seed, and with ``telemetry=``;
* a warm what-if answered again with a new key and a new ``k`` compiles
  nothing and issues no eager primitive: the call is one dispatch.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax._src import core as jax_core

from repro.cluster.orchestrator import OnlineAdmissionController, SpotCluster
from repro.core import engine as E
from repro.core.arrivals import Exponential
from repro.core.market import NoticeAwareKernel, SpotMarket, SpotPool
from repro.obs import Telemetry, timing

LAM, MU, K = 1 / 12, 1 / 24, 10.0
CHECKPOINT = 0.025
RS = np.linspace(0.5, 8.0, 16).astype(np.float32)  # the what-if's 16 r
MARKET = SpotMarket(pools=tuple(
    SpotPool(Exponential(MU / 4), price=p, hazard=h, notice=0.0333)
    for p, h in ((1.0, 0.05), (2.0, 0.03), (3.0, 0.02), (4.0, 0.01))))
KERNEL = NoticeAwareKernel(checkpoint_time=CHECKPOINT)


def _eager_sweep(params, *, k, n_events, key, n_seeds, impl, rng,
                 interpret=None, telemetry=None, prices=None, hazards=None,
                 rmax=16, chunk_events=E.DEFAULT_CHUNK_EVENTS, tile=256):
    """The same sweep with its prep run eagerly, one small program at a
    time, then the jitted executor: ``run_market_sweep`` as it was built
    before its prep moved into the executor's program."""
    params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
    k = jnp.asarray(k, jnp.float32)
    overrides = {"price": prices, "hazard": hazards}
    override_shapes = [jnp.asarray(v).shape[:-1] for v in overrides.values()
                       if v is not None and jnp.asarray(v).ndim > 1]
    grid_shape = jnp.broadcast_shapes(
        k.shape, *(x.shape for x in jax.tree.leaves(params)),
        *override_shapes)
    flat = lambda x: jnp.broadcast_to(x, grid_shape).reshape(-1)
    params_flat = jax.tree.map(flat, params)
    k_flat = flat(k)
    mp_flat = E._broadcast_config_params(MARKET.n_pools, MARKET.params(),
                                         overrides, grid_shape)
    preempt_on = MARKET.preemptible or hazards is not None
    keys = jax.random.split(key, n_seeds)
    chunk = min(chunk_events, n_events)
    interp = E._resolve_interpret("eager", impl, rng, interpret)
    if impl == "xla":
        stats = E._run_market_sweep_jit(
            Exponential(LAM), MARKET, KERNEL, rmax, preempt_on, n_events,
            chunk, 0, rng, params_flat, mp_flat, k_flat, keys,
            tel=telemetry)
    else:
        stats = E._run_market_sweep_pallas_jit(
            Exponential(LAM), MARKET, KERNEL, rmax, preempt_on, n_events,
            chunk, 0, tile, interp, params_flat, mp_flat, k_flat,
            E._raw_keys(keys), executor=impl, rng=rng, tel=telemetry)
    out = E.summarize_market(jax.device_get(stats), telemetry)
    return E._reshape_sweep(out, grid_shape, n_seeds)


#: name -> (params, keyword arguments of both calls)
CASES = {
    # the what-if's own shape: 16 r x 2 seeds on the slab stream
    "whatif_shape": ({"r": RS}, dict(k=K, n_seeds=2, rng="slab")),
    # a (3, 2) grid: r down, a per-point price vector across, one hazard
    "overrides": ({"r": np.array([[1.0], [2.5], [4.0]], np.float32)},
                  dict(k=np.array([8.0, 12.0], np.float32), n_seeds=2,
                       rng="split",
                       prices=np.linspace(0.5, 4.0, 24).reshape(3, 2, 4),
                       hazards=0.02)),
    "one_seed": ({"r": RS[:5]}, dict(k=K, n_seeds=1, rng="slab")),
    "telemetry": ({"r": RS[:4]}, dict(k=K, n_seeds=2, rng="slab",
                                      telemetry=Telemetry())),
}
EXECUTORS = {"xla": {"impl": "xla"}, "ref": {"impl": "ref"},
             "pallas": {"impl": "pallas", "interpret": True}}


@pytest.mark.parametrize("executor", list(EXECUTORS))
@pytest.mark.parametrize("case", list(CASES))
def test_one_program_answers_bitwise_as_the_eager_prep(case, executor):
    params, kw = CASES[case]
    kw = dict(kw, n_events=400, key=jax.random.key(17), chunk_events=200,
              rmax=16, **EXECUTORS[executor])
    got = E.run_market_sweep(Exponential(LAM), MARKET, KERNEL, params, **kw)
    want = _eager_sweep(params, **kw)
    assert got.keys() == want.keys()
    for name in got:
        a, b = jax.tree.leaves(got[name]), jax.tree.leaves(want[name])
        assert len(a) == len(b), name
        for x, y in zip(a, b):
            assert x.shape == y.shape, name
            assert np.array_equal(x, y), name


def _count_eager_primitives(monkeypatch):
    """Wrap JAX's eager primitive path; the returned list grows by one
    name for each primitive bound outside any trace."""
    seen = []
    process = jax_core.EvalTrace.process_primitive

    def counting(self, primitive, tracers, params):
        seen.append(primitive.name)
        return process(self, primitive, tracers, params)

    monkeypatch.setattr(jax_core.EvalTrace, "process_primitive", counting)
    return seen


def test_a_warm_what_if_is_one_dispatch(monkeypatch):
    """A new key and a new ``k`` of the same shape: no compile, and no
    eager primitive anywhere in the call (prep, dispatch, fetch)."""
    cluster = SpotCluster(job_process=Exponential(LAM), market=MARKET,
                          k_cost=K,
                          controller=OnlineAdmissionController(delta=12.0),
                          checkpoint_hours=CHECKPOINT)
    base = jax.random.key(5)
    keys = [jax.random.fold_in(base, i) for i in range(3)]
    first = cluster.what_if_sweep(RS, n_events=300, key=keys[0])
    compiles = timing.compile_count()
    seen = _count_eager_primitives(monkeypatch)
    again = cluster.what_if_sweep(RS, n_events=300, key=keys[1], k=7.5)
    other = cluster.what_if_sweep(RS, n_events=300, key=keys[2])
    assert seen == []
    assert timing.compile_count() == compiles
    assert not np.array_equal(first["avg_cost"], again["avg_cost"])
    assert not np.array_equal(first["avg_cost"], other["avg_cost"])
