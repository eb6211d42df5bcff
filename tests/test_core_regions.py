"""Multi-region routing subsystem tests.

The acceptance contract of the region engine (repro.core.regions +
repro.core.engine's region loop):

  * a **degenerate** topology (1 region, zero hazard, unit price) with a
    non-routing kernel reproduces the PR-3 engine **bit-for-bit** per seed
    under ALL THREE executors — run_region_sim / run_region_sweep are
    indistinguishable from run_sim / run_sweep (and, with market kernels
    and region economics, from the 1-pool run_market_sim) — property-tested
    across random configs and tile sizes;
  * all scalar statistics are exactly invariant under region *relabeling*
    (permuting regions with their tags) — per-region PRNG streams are keyed
    by region tag, not position;
  * routing rules behave as named: ``cheapest`` concentrates admissions on
    the cheapest region, ``home`` never crosses regions, ``weighted``
    follows traced logits, and capacity partitions are respected (a full
    region rejects even when another partition has room under ``home``);
  * the pooled region knapsack LP lower-bounds the engine and the routed
    bound is never worse than the home-only bound (the value of routing);
  * the Theorem-1 region identity holds exactly on preemption-free runs;
  * the host MultiRegionCluster mirrors the engine's routing semantics and
    its ``what_if_sweep`` runs on-device grids against the same topology.
"""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare interpreter: deterministic fallback (see
    from _propcheck import given, settings, st  # requirements-dev.txt)

import jax
import jax.numpy as jnp

from repro.core import (
    Exponential,
    Gamma,
    NoticeAwareKernel,
    Region,
    RegionTopology,
    RoutingKernel,
    SingleSlotKernel,
    SpotMarket,
    ThreePhaseKernel,
    Uniform,
    region_cost_lower_bound,
    region_knapsack_lp,
    run_market_sim,
    run_region_sim,
    run_region_sweep,
    run_sim,
    run_sweep,
    theorem1_region_cost,
)
from repro.core.engine import INT_STATS
from repro.core.waittime import DeterministicWait

LAM, MU, K = 1 / 12, 1 / 24, 10.0


def _hetero_topology(hazard_scale: float = 1.0) -> RegionTopology:
    return RegionTopology(regions=(
        Region(Exponential(LAM / 4), Exponential(1 / 30.0), price=0.5,
               hazard=0.02 * hazard_scale, notice=0.5, rmax=16),
        Region(Exponential(LAM / 2), Exponential(1 / 40.0), price=0.3,
               hazard=0.05 * hazard_scale, notice=0.01, rmax=8),
        Region(Exponential(LAM / 8), Exponential(1 / 60.0), price=0.2,
               rmax=4),
        Region(Exponential(LAM / 8), Exponential(1 / 90.0), price=0.1,
               hazard=0.10 * hazard_scale, notice=2.0, rmax=16),
    ))


def assert_stats_equal(a: dict, b: dict, context=""):
    for name, v in a.items():
        np.testing.assert_array_equal(
            np.asarray(v), np.asarray(b[name]),
            err_msg=f"{name} diverged ({context})")


def assert_stats_close(xla: dict, pal: dict, context=""):
    """The cross-layout contract vs the production XLA executor: integer
    event accounting bitwise, float sums to ~ulp rtol."""
    for name, v in xla.items():
        if name in INT_STATS:
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(pal[name]),
                err_msg=f"{name} diverged ({context})")
        else:
            np.testing.assert_allclose(
                np.asarray(v), np.asarray(pal[name]), rtol=1e-5,
                err_msg=f"{name} diverged ({context})")


# ---------------------------------------------------------------------------
# Degenerate topology == PR-3 engine, bit for bit
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "job,spot,r",
    [
        (Exponential(LAM), Exponential(MU), 1.5),
        (Gamma(12.0, 1.0), Exponential(MU), 3.0),
        (Exponential(LAM), Uniform(0.0, 48.0), 2.5),
        (Exponential(LAM), Exponential(MU), 0.0),
    ],
    ids=["mm", "gm", "mu", "r0"],
)
def test_degenerate_region_bit_for_bit(job, spot, r):
    key = jax.random.key(7)
    kernel = ThreePhaseKernel()
    ref = run_sim(job, spot, kernel, {"r": jnp.float32(r)}, k=K,
                  n_events=30_000, key=key)
    new = run_region_sim(RegionTopology.single(job, spot), kernel,
                         {"r": jnp.float32(r)}, k=K, n_events=30_000,
                         key=key)
    for name, v in ref.items():
        assert new[name] == v, name  # identical to the last bit
    assert new["preemptions"] == 0.0 and new["resumed"] == 0.0
    assert new["spot_cost"] == new["spot_served"]  # unit price
    # every admission stays home, every serve lands in region 0
    assert new["cross_region_frac"] == 0.0
    assert new["region_served"][0] == new["spot_served"]
    assert new["region_routed"][0] == new["routed_home"]


def test_degenerate_region_bit_for_bit_single_slot_and_chunked():
    job, spot = Exponential(LAM), Exponential(MU)
    kernel = SingleSlotKernel(wait=DeterministicWait(5.0))
    key = jax.random.key(3)
    ref = run_sim(job, spot, kernel, {}, k=K, n_events=30_000, key=key,
                  rmax=1, chunk_events=4096)
    new = run_region_sim(RegionTopology.single(job, spot, rmax=1), kernel,
                         {}, k=K, n_events=30_000, key=key,
                         chunk_events=4096)
    for name, v in ref.items():
        assert new[name] == v, name


def test_degenerate_region_vs_market_with_economics():
    """A 1-region topology with price/hazard/notice is the 1-pool market,
    bit for bit — including the preemption path and a market-protocol
    kernel (admit_market + on_preempt)."""
    job, spot = Exponential(LAM), Exponential(1 / 40.0)
    kernel = NoticeAwareKernel(checkpoint_time=0.05)
    key = jax.random.key(11)
    mkt = run_market_sim(job, SpotMarket.single(spot, price=0.4, hazard=0.05,
                                                notice=1.0),
                         kernel, kernel.init_params(2.0), k=K,
                         n_events=30_000, key=key, chunk_events=4096)
    reg = run_region_sim(RegionTopology.single(job, spot, price=0.4,
                                               hazard=0.05, notice=1.0),
                         kernel, kernel.init_params(2.0), k=K,
                         n_events=30_000, key=key, chunk_events=4096)
    assert mkt["preemptions"] > 0 and mkt["resumed"] > 0  # the path is live
    for name, v in mkt.items():
        if name.startswith("pool_"):
            np.testing.assert_array_equal(
                np.asarray(reg[name.replace("pool_", "region_")]),
                np.asarray(v), err_msg=name)
        else:
            np.testing.assert_array_equal(np.asarray(reg[name]),
                                          np.asarray(v), err_msg=name)


@settings(max_examples=8, deadline=None)
@given(
    r_lo=st.floats(min_value=0.0, max_value=3.0),
    rmax=st.integers(min_value=1, max_value=12),
    chunk=st.sampled_from([256, 1000, 4096]),
    tile=st.sampled_from([1, 3, 256]),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_degenerate_region_sweep_bitwise_property(r_lo, rmax, chunk, tile,
                                                  seed):
    """The ISSUE-4 frozen contract: a 1-region ``run_region_sweep`` is
    bitwise-identical to ``run_sweep`` across random configs and tile
    sizes, under all three executors."""
    job, spot = Exponential(LAM), Exponential(MU)
    params = {"r": jnp.linspace(r_lo, r_lo + 2.0, 3)}
    topo = RegionTopology.single(job, spot, rmax=rmax)
    kw = dict(k=K, n_events=2_000, key=jax.random.key(seed), n_seeds=2,
              chunk_events=chunk, burn_in=128)
    for impl in ("xla", "ref"):
        ref = run_sweep(job, spot, ThreePhaseKernel(), params, rmax=rmax,
                        impl=impl, **kw)
        new = run_region_sweep(topo, ThreePhaseKernel(), params, impl=impl,
                               **kw)
        assert_stats_equal(ref, new, f"impl={impl} seed={seed}")
    ref = run_sweep(job, spot, ThreePhaseKernel(), params, rmax=rmax,
                    impl="pallas", interpret=True, tile=tile, **kw)
    new = run_region_sweep(topo, ThreePhaseKernel(), params, impl="pallas",
                           interpret=True, tile=tile, **kw)
    assert_stats_equal(ref, new, f"impl=pallas tile={tile} seed={seed}")


# ---------------------------------------------------------------------------
# Executor equivalence on heterogeneous topologies (the PR-3 ledger, grown
# a region axis)
# ---------------------------------------------------------------------------
def test_region_sweep_pallas_bit_for_bit():
    topo = _hetero_topology()
    kern = RoutingKernel(NoticeAwareKernel(checkpoint_time=0.05),
                         choice="least_loaded")
    params = {"r": jnp.linspace(0.5, 4.0, 4)}
    kw = dict(k=K, n_events=5_000, key=jax.random.key(0), n_seeds=2,
              chunk_events=2_048)
    ref = run_region_sweep(topo, kern, params, impl="ref", **kw)
    pal = run_region_sweep(topo, kern, params, impl="pallas",
                           interpret=True, **kw)
    assert_stats_equal(ref, pal, "hetero-routing")
    assert_stats_close(run_region_sweep(topo, kern, params, **kw), pal,
                       "hetero-routing")


def test_region2_revocations_slab_pallas_bit_for_bit():
    """The two spot regions the chip benchmark runs (``region2_least_
    loaded``: even demand and supply, the Advisor bands' hazards, a
    two-minute notice, a 90 s checkpoint, least-loaded routing) on the
    slab stream, with the hazards scaled up so that revocations and
    resumes occur at this size: the interpreted kernel is the scan
    oracle bit for bit."""
    hazard_scale = 200.0
    topo = RegionTopology(regions=tuple(
        Region(Exponential(1 / 24), Exponential(1 / 48), price=price,
               hazard=hazard * hazard_scale, notice=1 / 30, rmax=64)
        for price, hazard in ((1.0, 0.0002635231406129536),
                              (2.0, 3.46819287456026e-05))))
    kern = RoutingKernel(NoticeAwareKernel(checkpoint_time=0.025),
                         choice="least_loaded")
    params = {"r": jnp.array([0.75, 1.5, 2.0, 3.25])}
    kw = dict(k=K, n_events=3_000, key=jax.random.key(16), n_seeds=3,
              burn_in=500, chunk_events=1_024, rng="slab")
    ref = run_region_sweep(topo, kern, params, impl="ref", **kw)
    pal = run_region_sweep(topo, kern, params, impl="pallas",
                           interpret=True, tile=8, **kw)
    assert_stats_equal(ref, pal, "region2 slab")
    assert (np.asarray(pal["resumed"]) > 0).any()
    assert (np.asarray(pal["region_preempted"]) > 0).all()


# ---------------------------------------------------------------------------
# Property: statistics exactly invariant under region relabeling
# ---------------------------------------------------------------------------
_SCALAR_INVARIANTS = ("avg_cost", "avg_delay", "pi0_time", "pi0_spot",
                      "spot_utilization", "jobs_arrived", "spot_served",
                      "ondemand", "preemptions", "resumed", "spot_cost",
                      "routed_home", "cross_region_frac", "time")


@settings(max_examples=6, deadline=None)
@given(perm=st.sampled_from([(1, 0, 2, 3), (3, 2, 1, 0), (2, 3, 0, 1),
                             (1, 2, 3, 0)]),
       r=st.floats(min_value=0.5, max_value=4.0))
def test_region_relabeling_invariance(perm, r):
    topo = _hetero_topology()
    kernel = RoutingKernel(NoticeAwareKernel(checkpoint_time=0.05),
                           choice="cheapest")
    kw = dict(k=K, n_events=15_000, key=jax.random.key(11),
              chunk_events=4096)
    res = run_region_sim(topo, kernel, {"r": jnp.float32(r)}, **kw)
    res_p = run_region_sim(topo.relabel(list(perm)), kernel,
                           {"r": jnp.float32(r)}, **kw)
    for name in _SCALAR_INVARIANTS:
        assert res[name] == res_p[name], name  # exact, not approximate
    inv = [list(perm).index(i) for i in range(4)]
    for name in ("region_served", "region_spot_arrivals", "region_preempted",
                 "region_jobs", "region_routed"):
        np.testing.assert_array_equal(np.asarray(res[name]),
                                      np.asarray(res_p[name])[inv],
                                      err_msg=name)


# ---------------------------------------------------------------------------
# Routing semantics
# ---------------------------------------------------------------------------
def test_routing_rules():
    topo = _hetero_topology(hazard_scale=0.0)
    kw = dict(k=K, n_events=20_000, key=jax.random.key(8))
    cheapest = run_region_sim(topo, RoutingKernel(ThreePhaseKernel(),
                                                  choice="cheapest"),
                              {"r": jnp.float32(3.0)}, **kw)
    # all admissions target region 3 (price 0.1)
    assert np.asarray(cheapest["region_routed"])[:3].sum() == 0
    assert cheapest["cross_region_frac"] > 0.0
    home = run_region_sim(topo, ThreePhaseKernel(),  # no route hook
                          {"r": jnp.float32(3.0)}, **kw)
    assert home["cross_region_frac"] == 0.0
    assert home["routed_home"] == np.asarray(home["region_routed"]).sum()
    # demand follows the per-region job rates under home routing
    jobs = np.asarray(home["region_jobs"])
    assert jobs[1] > jobs[2] and jobs[1] > jobs[3]
    weighted = run_region_sim(
        topo, RoutingKernel(ThreePhaseKernel(), choice="weighted"),
        {"r": jnp.float32(3.0),
         "region_logits": jnp.array([-9.0, -9.0, 9.0, -9.0])}, **kw)
    routed = np.asarray(weighted["region_routed"])
    assert routed[2] > 0 and routed[[0, 1, 3]].sum() == 0
    spread = run_region_sim(topo, RoutingKernel(ThreePhaseKernel(),
                                                choice="least_loaded"),
                            {"r": jnp.float32(3.0)}, **kw)
    assert (np.asarray(spread["region_routed"]) > 0).all()


def test_capacity_partitions_are_respected():
    """rmax_r gates each region separately: under home routing a full
    region rejects to on-demand even while another partition is empty."""
    topo = RegionTopology(regions=(
        Region(Exponential(1.0), Exponential(1e-6), rmax=1),  # swamped
        Region(Exponential(1e-6), Exponential(1.0), rmax=64),  # idle
    ))
    res = run_region_sim(topo, ThreePhaseKernel(), {"r": jnp.float32(8.0)},
                         k=K, n_events=4_000, key=jax.random.key(2))
    region_routed = np.asarray(res["region_routed"])
    assert region_routed[0] >= 1 and region_routed[1] == 0
    assert res["ondemand"] > 0  # overflow went on-demand, not cross-region
    assert np.asarray(res["region_served"])[1] == 0


def test_routing_beats_home_only_on_skewed_topology():
    """Hot demand in a pricey region + idle cheap capacity elsewhere: the
    least-loaded router must beat home-only cost (CRN seeds, wide margin)."""
    mk = lambda: RegionTopology(regions=(
        Region(Exponential(LAM), Exponential(MU / 8), price=0.9, rmax=16),
        Region(Exponential(LAM / 50), Exponential(MU), price=0.1, rmax=16),
    ))
    kw = dict(k=K, n_events=40_000, key=jax.random.key(5), n_seeds=2)
    home = run_region_sweep(mk(), ThreePhaseKernel(),
                            {"r": jnp.float32(4.0)}, **kw)
    routed = run_region_sweep(mk(), RoutingKernel(ThreePhaseKernel(),
                                                  choice="least_loaded"),
                              {"r": jnp.float32(4.0)}, **kw)
    assert routed["avg_cost_job"].mean() < home["avg_cost_job"].mean() - 0.5
    assert routed["cross_region_frac"].mean() > 0.1


# ---------------------------------------------------------------------------
# Batched region sweeps: one jit over (params × k × regions-config × seeds)
# ---------------------------------------------------------------------------
def test_region_sweep_matches_per_point_calls():
    topo = _hetero_topology()
    kernel = RoutingKernel(NoticeAwareKernel(checkpoint_time=0.05),
                           choice="cheapest")
    rs = jnp.linspace(0.5, 4.0, 6)
    key = jax.random.key(0)
    out = run_region_sweep(topo, kernel, {"r": rs}, k=K, n_events=10_000,
                           key=key, n_seeds=2)
    assert out["avg_cost"].shape == (6, 2)
    assert out["region_served"].shape == (6, 2, 4)
    seed_keys = jax.random.split(key, 2)
    for i in (0, 5):
        for s in range(2):
            pt = run_region_sim(topo, kernel, {"r": rs[i]}, k=K,
                                n_events=10_000, key=seed_keys[s])
            assert pt["jobs_arrived"] == out["jobs_arrived"][i, s]
            np.testing.assert_allclose(out["avg_cost"][i, s],
                                       pt["avg_cost"], rtol=1e-6)
            np.testing.assert_array_equal(
                np.asarray(pt["region_routed"]),
                np.asarray(out["region_routed"])[i, s])


def test_region_sweep_regions_config_axis():
    """The region configuration itself is a grid axis of one compiled call
    — including the demand axis (job_scales) the market engine lacks."""
    topo = _hetero_topology()
    kernel = RoutingKernel(NoticeAwareKernel(checkpoint_time=0.05),
                           choice="cheapest")
    scale = np.linspace(0.5, 2.0, 5)
    price_grid = topo.prices()[None, :] * scale[:, None]  # (5, R)
    out = run_region_sweep(topo, kernel, {"r": jnp.float32(3.0)}, k=K,
                           prices=price_grid, n_events=10_000,
                           key=jax.random.key(4), n_seeds=2)
    assert out["avg_cost"].shape == (5, 2)
    cost = out["avg_cost"].mean(-1)
    assert cost[0] < cost[-1]  # pricier regions -> pricier jobs
    # slowing demand everywhere cuts arrivals per (fixed-event) horizon
    out2 = run_region_sweep(topo, kernel, {"r": jnp.float32(3.0)}, k=K,
                            job_scales=np.array([1.0, 4.0])[:, None]
                            * np.ones((1, 4)),
                            n_events=10_000, key=jax.random.key(4),
                            n_seeds=1)
    assert (out2["jobs_arrived"][0] > out2["jobs_arrived"][1]).all()
    # hazard override on a statically hazard-free topology arms preemption
    out3 = run_region_sweep(topo.relabel([0, 1, 2, 3]), kernel,
                            {"r": jnp.float32(3.0)}, k=K, hazards=0.05,
                            n_events=10_000, key=jax.random.key(4),
                            n_seeds=1)
    assert (out3["preemptions"] > 0).all()


# ---------------------------------------------------------------------------
# Region LP + Theorem-1 generalization
# ---------------------------------------------------------------------------
def test_region_lp_degenerate_and_routing_value():
    # 1 region, unit price: the paper's min(1, λδ) bound
    topo1 = RegionTopology.single(Exponential(LAM), Exponential(MU))
    from repro.core import cost_lower_bound
    for delta in (3.0, 27.0):
        out = region_knapsack_lp(K, delta, topo1)
        np.testing.assert_allclose(out["objective"],
                                   cost_lower_bound(K, LAM, MU, delta),
                                   rtol=1e-12)
    # pooling demand against all supply can only improve the floor
    topo = _hetero_topology()
    for delta in (3.0, 27.0):
        routed = region_cost_lower_bound(K, delta, topo, routed=True)
        home = region_cost_lower_bound(K, delta, topo, routed=False)
        assert routed <= home + 1e-12
    # preemption-priced effective costs weaken (raise) the floor
    assert (region_cost_lower_bound(K, 27.0, topo, include_preemption=True)
            >= region_cost_lower_bound(K, 27.0, topo) - 1e-12)


def test_theorem1_region_cost_identity_on_engine_run():
    topo = _hetero_topology(hazard_scale=0.0)  # preemption-free identity
    kernel = RoutingKernel(ThreePhaseKernel(), choice="uniform")
    res = run_region_sim(topo, kernel, {"r": jnp.float32(4.0)}, k=K,
                         n_events=60_000, key=jax.random.key(9),
                         chunk_events=4096)
    # exact empirical identity: (k - avg_cost) * completed
    #   == sum_r (k - c_r) * served_r
    lhs = (K - res["avg_cost"]) * res["jobs_completed"]
    rhs = ((K - topo.prices()) * np.asarray(res["region_served"])).sum()
    np.testing.assert_allclose(lhs, rhs, rtol=2e-5)
    # population form: empirical rates + utilizations plug into the law
    lam_emp = res["arrival_rate"]
    rates_emp = np.asarray(res["region_spot_arrivals"]) / res["time"]
    pred = theorem1_region_cost(K, lam_emp, rates_emp, topo.prices(),
                                np.asarray(res["region_utilization"]))
    np.testing.assert_allclose(pred, res["avg_cost"], rtol=1e-3)
    # the engine respects the pooled LP floor at the realized delay
    lp = region_knapsack_lp(K, res["avg_delay_job"], topo)
    assert res["avg_cost_job"] > lp["objective"] - 0.3


# ---------------------------------------------------------------------------
# Host-side routing: MultiRegionCluster
# ---------------------------------------------------------------------------
def test_multi_region_cluster_mirrors_engine_semantics():
    from repro.cluster.orchestrator import (MultiRegionCluster,
                                            OnlineAdmissionController)

    topo = _hetero_topology()
    ctl = OnlineAdmissionController(delta=27.0, r0=3.0, eta=0.0)
    cluster = MultiRegionCluster(topology=topo, controller=ctl,
                                 route="cheapest", checkpoint_hours=0.05,
                                 seed=3)
    stats = cluster.run(8_000)
    assert stats.jobs_completed > 0 and stats.preemptions > 0
    # cheapest routing: only the cheapest region's queue is ever fed
    assert sum(stats.region_routed[:3]) == 0
    assert sum(stats.region_served[:3]) == 0
    # leg accounting conserves cost exactly like the engine
    spot_spend = stats.spot_cost
    np.testing.assert_allclose(
        stats.total_cost, spot_spend + K * stats.ondemand_served, rtol=1e-9)
    # the on-device what-if grid runs against the same topology
    out = cluster.what_if_sweep([1.0, 3.0], n_events=3_000, n_seeds=2)
    assert out["avg_cost_job"].shape == (2, 2)
    assert out["region_routed"].shape == (2, 2, 4)
    assert np.asarray(out["region_routed"])[:, :, :3].sum() == 0


def test_topology_validation_and_views():
    with pytest.raises(ValueError, match="at least one region"):
        RegionTopology(regions=())
    with pytest.raises(ValueError, match="unique"):
        RegionTopology(regions=(
            Region(Exponential(1.0), Exponential(1.0), tag=0),
            Region(Exponential(1.0), Exponential(1.0), tag=0)))
    topo = _hetero_topology()
    assert topo.total_slots == 16 + 8 + 4 + 16
    np.testing.assert_array_equal(topo.slot_offsets(), [0, 16, 24, 28])
    assert topo.preemptible and not topo.is_degenerate
    assert RegionTopology.single(Exponential(LAM),
                                 Exponential(MU)).is_degenerate
    np.testing.assert_allclose(topo.total_job_rate(), LAM, rtol=1e-12)
