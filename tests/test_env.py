"""The supply-shock contract (repro.core.env + the engine ``env=`` axis).

Frozen guarantees:

  * **Zero-cost off** — ``env=None`` lowers to byte-identical StableHLO
    (sha256 of the whole 24-cell loop × executor × rng matrix, frozen in
    tests/data/hlo_pr6.json) and a single-segment constant timeline
    reproduces the pre-env engine **bit-for-bit** on every loop ×
    executor × rng.
  * **Shock accounting is exact** — storms/blackouts/spikes observed
    equal the timeline's injected counts; shock dwell times are exact
    (the boundary-as-event design means no event interval straddles a
    segment); degradation is bounded by exposure.
  * **Graceful degradation** — :class:`repro.core.market.PanicKernel`
    is the identity without blackouts (bitwise) and routes admissions
    around dead pools/regions under one; the Algorithm-1 learner stays
    finite and bounded across regime flips with the guardrails on.
  * **Loud failure** — malformed timelines, override grids, and run
    plans raise actionable ``ValueError``s at the host boundary, and
    poisoned (NaN/inf) windows raise :class:`NonFiniteStatsError`
    instead of leaking silent NaN averages.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    EnvTimeline,
    Exponential,
    NonFiniteStatsError,
    PanicKernel,
    inject_blackout,
    inject_price_spike,
    inject_storm,
    markov_timeline,
    run_market_sim,
    run_market_sweep,
    run_region_sim,
    run_region_sweep,
    run_sim,
    run_sweep,
)
from repro.core.adaptive import adaptive_admission_control
from repro.core.engine import _check_finite_stats
from repro.core.env import Regime, SEG_STORM
from repro.core.market import NoticeAwareKernel, SpotMarket, SpotPool
from repro.core.policies import ThreePhaseKernel
from repro.core.regions import Region, RegionTopology, RoutingKernel

_BASELINE = Path(__file__).parent / "data" / "hlo_pr6.json"

N_EVENTS, CHUNK = 2500, 1024
KEY = jax.random.key(7)


def _market() -> SpotMarket:
    return SpotMarket(pools=(
        SpotPool(arrival=Exponential(0.9), price=1.0, hazard=0.3,
                 notice=0.1),
        SpotPool(arrival=Exponential(0.5), price=0.6, hazard=0.8,
                 notice=0.3),
    ))


def _topo() -> RegionTopology:
    return RegionTopology(regions=(
        Region(job=Exponential(1.2), spot=Exponential(0.9), price=1.0,
               hazard=0.3, notice=0.1, rmax=4),
        Region(job=Exponential(0.7), spot=Exponential(0.5), price=0.6,
               hazard=0.8, notice=0.3, rmax=4),
    ))


def _shock_timeline() -> EnvTimeline:
    tl = EnvTimeline.constant()
    tl = inject_storm(tl, 100.0, 400.0, hazard_mult=6.0)
    tl = inject_blackout(tl, 600.0, 800.0, loc=1, n_locs=2)
    return tl


def _run(loop: str, impl: str, rng: str, env, kernel=None) -> dict:
    kw = dict(k=10.0, n_events=N_EVENTS, key=KEY, burn_in=256,
              chunk_events=CHUNK, impl=impl, rng=rng, interpret=True,
              tile=2, env=env)
    if loop == "single":
        return run_sim(Exponential(1.2), Exponential(0.9),
                       ThreePhaseKernel(), {"r": jnp.float32(2.0)}, **kw)
    if loop == "market":
        kern = kernel or NoticeAwareKernel(checkpoint_time=0.05)
        return run_market_sim(Exponential(1.2), _market(), kern,
                              {"r": jnp.float32(2.0)}, **kw)
    kern = kernel or RoutingKernel(base=NoticeAwareKernel(
        checkpoint_time=0.05), choice="cheapest")
    return run_region_sim(_topo(), kern, {"r": jnp.float32(2.0)}, **kw)


def _assert_bitwise(a: dict, b: dict, extra_keys_ok: bool = False) -> None:
    keys = a.keys() if not extra_keys_ok else (a.keys() & b.keys())
    for name in keys:
        av, bv = np.asarray(a[name]), np.asarray(b[name])
        assert av.shape == bv.shape and (av == bv).all(), (
            f"{name}: {av} != {bv}")


# ---------------------------------------------------------------------------
# Zero-cost off
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl", ["xla", "pallas", "ref"])
@pytest.mark.parametrize("rng", ["split", "slab"])
@pytest.mark.parametrize("loop", ["single", "market", "region"])
def test_constant_timeline_is_bitwise_off(loop, impl, rng):
    """A single-segment constant timeline == env=None, bit for bit, on
    every loop × executor × rng (the base keys; env adds its counters)."""
    off = _run(loop, impl, rng, env=None)
    on = _run(loop, impl, rng, env=EnvTimeline.constant())
    for name in off:
        av, bv = np.asarray(off[name]), np.asarray(on[name])
        assert av.shape == bv.shape and (av == bv).all(), (loop, impl, rng,
                                                           name)
    assert on["env_boundaries"] == 0
    assert on["storm_time"] == 0.0 and on["blackout_time"] == 0.0


@pytest.mark.parametrize("rng", ["split", "slab"])
def test_constant_timeline_sweep_bitwise_off(rng):
    """Sweep entries (grid × seeds lanes) obey the same off-contract."""
    kw = dict(k=10.0, n_events=2000, key=KEY, n_seeds=2, burn_in=128,
              chunk_events=1024, rng=rng)
    a = run_market_sweep(Exponential(1.2), _market(),
                         NoticeAwareKernel(checkpoint_time=0.05),
                         {"r": jnp.float32([1.5, 2.5])}, **kw)
    b = run_market_sweep(Exponential(1.2), _market(),
                         NoticeAwareKernel(checkpoint_time=0.05),
                         {"r": jnp.float32([1.5, 2.5])},
                         env=EnvTimeline.constant(), **kw)
    for name in a:
        assert (np.asarray(a[name]) == np.asarray(b[name])).all(), name
    c = run_sweep(Exponential(1.2), Exponential(0.9), ThreePhaseKernel(),
                  {"r": jnp.float32([1.5, 2.5])}, **kw)
    d = run_sweep(Exponential(1.2), Exponential(0.9), ThreePhaseKernel(),
                  {"r": jnp.float32([1.5, 2.5])},
                  env=EnvTimeline.constant(), **kw)
    for name in c:
        assert (np.asarray(c[name]) == np.asarray(d[name])).all(), name
    e = run_region_sweep(_topo(), RoutingKernel(
        base=NoticeAwareKernel(checkpoint_time=0.05), choice="cheapest"),
        {"r": jnp.float32([1.5, 2.5])}, **kw)
    f = run_region_sweep(_topo(), RoutingKernel(
        base=NoticeAwareKernel(checkpoint_time=0.05), choice="cheapest"),
        {"r": jnp.float32([1.5, 2.5])}, env=EnvTimeline.constant(), **kw)
    for name in e:
        assert (np.asarray(e[name]) == np.asarray(f[name])).all(), name


def test_env_off_lowering_frozen():
    """The engine compiles the byte-identical programs of the frozen
    baseline: sha256 of the lowered StableHLO for every cell of
    tests/_hlo_matrix.py — the 24 (loop × executor × rng) cells with
    every optional axis off, the three ``shard="lanes"`` programs,
    ``run_market_sweep``'s one jitted front, and each loop's executors
    with the telemetry, env and work axes on.  The baseline was frozen
    with tools/freeze_hlo_baseline.py from the engine of commit 2918bde
    under jax 0.9.0 on the CPU backend (the one before it dated from
    jax 0.4.37, so this test skipped under 0.9.0).  Lowered in a fresh
    subprocess with XLA_FLAGS scrubbed — other test modules override the
    host device count in-process, which perturbs lowered text."""
    baseline = json.loads(_BASELINE.read_text())
    here = Path(__file__).parent
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["PYTHONPATH"] = os.pathsep.join(
        [str(here.parent / "src"), env.get("PYTHONPATH", "")])
    proc = subprocess.run(
        [sys.executable, str(here / "_hlo_matrix.py")],
        capture_output=True, text=True, env=env, check=True)
    fresh = json.loads(proc.stdout)
    for k, v in fresh["tag"].items():
        if baseline[k] != v:
            pytest.skip(f"baseline frozen under {k}={baseline[k]}, "
                        f"running {v}")
    digests = fresh["digests"]
    assert digests.keys() == baseline["digests"].keys()
    moved = [k for k, v in digests.items() if baseline["digests"][k] != v]
    assert not moved, f"lowering changed for cells: {moved}"


# ---------------------------------------------------------------------------
# Exact shock accounting
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl,rng", [("xla", "split"), ("xla", "slab"),
                                      ("pallas", "slab")])
@pytest.mark.parametrize("loop", ["market", "region"])
def test_shock_counter_identities(loop, impl, rng):
    tl = _shock_timeline()
    kw = dict(k=10.0, n_events=6000, key=KEY, burn_in=0, chunk_events=2048,
              impl=impl, rng=rng, interpret=True, tile=2, env=tl)
    if loop == "market":
        out = run_market_sim(Exponential(1.2), _market(),
                             NoticeAwareKernel(checkpoint_time=0.05),
                             {"r": jnp.float32(2.0)}, **kw)
    else:
        out = run_region_sim(_topo(), RoutingKernel(
            base=NoticeAwareKernel(checkpoint_time=0.05),
            choice="cheapest"), {"r": jnp.float32(2.0)}, **kw)
    assert out["storms_observed"] == tl.count_storms() == 1
    assert out["blackouts_observed"] == tl.count_blackouts() == 1
    assert out["env_boundaries"] == 4  # enter/leave storm, enter/leave blk
    # dwell times are exact: dt never spans a segment boundary
    np.testing.assert_allclose(out["storm_time"], 300.0, rtol=1e-5)
    np.testing.assert_allclose(out["blackout_time"], 200.0, rtol=1e-5)
    assert out["degraded_admits"] <= out["shock_arrivals"]


def test_single_loop_blackout_starves_spot():
    """Single-loop blackout: spot supply vanishes over the window, so no
    spot serves can land inside it (clocks are inflated, not dropped)."""
    tl = inject_blackout(EnvTimeline.constant(), 200.0, 500.0)
    out = run_sim(Exponential(1.2), Exponential(0.9), ThreePhaseKernel(),
                  {"r": jnp.float32(2.0)}, k=10.0, n_events=4000, key=KEY,
                  burn_in=0, env=tl)
    assert out["blackouts_observed"] == 1
    assert out["shock_served"] == 0
    np.testing.assert_allclose(out["blackout_time"], 300.0, rtol=1e-5)


def test_markov_timeline_is_valid_and_counted():
    regimes = (Regime(mean_hold=50.0),
               Regime(mean_hold=10.0, hazard_mult=5.0, kind=SEG_STORM))
    tl = markov_timeline(regimes, horizon=500.0, seed=3)
    assert tl.n_segments >= 2 and 0.0 < tl.span() <= 500.0
    out = run_market_sim(Exponential(1.2), _market(),
                         NoticeAwareKernel(checkpoint_time=0.05),
                         {"r": jnp.float32(2.0)}, k=10.0, n_events=4000,
                         key=KEY, burn_in=0, env=tl)
    assert out["storms_observed"] <= tl.count_storms()
    assert out["env_boundaries"] >= out["storms_observed"]


# ---------------------------------------------------------------------------
# Graceful degradation: PanicKernel
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("impl,rng", [("xla", "split"), ("xla", "slab"),
                                      ("pallas", "slab"), ("ref", "split")])
@pytest.mark.parametrize("loop", ["market", "region"])
def test_panic_kernel_identity_without_blackout(loop, impl, rng):
    """No blackout in the timeline → PanicKernel == its base, bitwise."""
    base = (NoticeAwareKernel(checkpoint_time=0.05) if loop == "market"
            else RoutingKernel(base=NoticeAwareKernel(checkpoint_time=0.05),
                               choice="cheapest"))
    a = _run(loop, impl, rng, env=None, kernel=base)
    b = _run(loop, impl, rng, env=None, kernel=PanicKernel(base=base))
    _assert_bitwise(a, b)


def test_panic_kernel_routes_around_dead_pool():
    """Blackout on the cheap pool: the base kernel strands admissions on
    it; PanicKernel re-targets the live pool, which then serves."""
    job = Exponential(1.2)
    market = SpotMarket(pools=(
        SpotPool(arrival=Exponential(1.1), price=1.0, hazard=0.3,
                 notice=0.1),
        SpotPool(arrival=Exponential(1.5), price=0.6, hazard=0.8,
                 notice=0.3),
    ))
    base = NoticeAwareKernel(checkpoint_time=0.05)
    tl = inject_blackout(EnvTimeline.constant(), 300.0, 700.0, loc=1,
                         n_locs=2)
    kw = dict(k=10.0, n_events=8000, key=KEY, burn_in=0, chunk_events=2048,
              impl="xla", rng="slab", env=tl)
    plain = run_market_sim(job, market, base, {"r": jnp.float32(3.0)}, **kw)
    panic = run_market_sim(job, market, PanicKernel(base=base),
                           {"r": jnp.float32(3.0)}, **kw)
    assert plain["pool_served"][0] == 0  # cheapest-rule never leaves pool 1
    assert panic["pool_served"][0] > 0  # failover lands work on the live one
    assert panic["degraded_admits"] < plain["degraded_admits"]
    assert panic["avg_cost"] < plain["avg_cost"]


def test_panic_kernel_reroutes_dead_region():
    """Region blackout: a panic-wrapped routing kernel sends cross-region
    traffic around the dead region."""
    tl = inject_blackout(EnvTimeline.constant(), 300.0, 700.0, loc=1,
                         n_locs=2)
    rkern = RoutingKernel(base=NoticeAwareKernel(checkpoint_time=0.05),
                          choice="cheapest")
    kw = dict(k=10.0, n_events=6000, key=KEY, burn_in=0, chunk_events=2048,
              impl="xla", rng="slab", env=tl)
    plain = run_region_sim(_topo(), rkern, {"r": jnp.float32(2.0)}, **kw)
    panic = run_region_sim(_topo(), PanicKernel(base=rkern),
                           {"r": jnp.float32(2.0)}, **kw)
    assert panic["degraded_admits"] < plain["degraded_admits"]


# ---------------------------------------------------------------------------
# Graceful degradation: learner guardrails
# ---------------------------------------------------------------------------
def test_learner_survives_regime_flips():
    tl = inject_storm(EnvTimeline.constant(), 20.0, 200.0, hazard_mult=8.0)
    tl = inject_price_spike(tl, 300.0, 500.0, price_mult=3.0)
    job = Exponential(1.0)
    market = SpotMarket(pools=(SpotPool(arrival=Exponential(1.3), price=1.0,
                                        hazard=0.2, notice=0.1),))
    out = adaptive_admission_control(
        job, market, k=10.0, delta=2.0, eta=0.1, r0=1.0, window_events=512,
        n_windows=40, key=jax.random.key(0), env=tl, max_step=0.5,
        shock_reset=True)
    r = np.asarray(out["r"])
    assert np.isfinite(r).all()
    assert (r >= 0.0).all() and (r <= 16.0).all()
    # the clamp bounds every excursion except the shock_reset jumps back
    # toward r0=1.0 (which only ever shrink r here)
    dr = np.diff(r)
    assert ((dr <= 0.5 + 1e-6) | (r[1:] == 1.0)).all()
    # guardrails off at defaults: identical signature still works
    base = adaptive_admission_control(
        job, market, k=10.0, delta=2.0, eta=0.1, r0=1.0, window_events=512,
        n_windows=5, key=jax.random.key(0))
    assert np.isfinite(np.asarray(base["r"])).all()


# ---------------------------------------------------------------------------
# Loud failure: input validation at every entry point
# ---------------------------------------------------------------------------
def test_env_rejects_wrong_type():
    with pytest.raises(TypeError, match="EnvTimeline"):
        run_sim(Exponential(1.2), Exponential(0.9), ThreePhaseKernel(),
                {"r": jnp.float32(2.0)}, k=10.0, n_events=100, key=KEY,
                env={"not": "a timeline"})


def test_run_shape_validation():
    with pytest.raises(ValueError, match="n_events"):
        run_sim(Exponential(1.2), Exponential(0.9), ThreePhaseKernel(),
                {"r": jnp.float32(2.0)}, k=10.0, n_events=0, key=KEY)
    with pytest.raises(ValueError, match="burn_in"):
        run_market_sim(Exponential(1.2), _market(),
                       NoticeAwareKernel(), {"r": jnp.float32(2.0)},
                       k=10.0, n_events=100, burn_in=-1, key=KEY)


def test_loc_override_validation():
    with pytest.raises(ValueError, match="last-axis length 2"):
        run_market_sweep(Exponential(1.2), _market(), NoticeAwareKernel(),
                         {"r": jnp.float32(2.0)}, k=10.0, n_events=100,
                         key=KEY, prices=jnp.ones((3,)))
    with pytest.raises(ValueError, match="non-negative"):
        run_market_sweep(Exponential(1.2), _market(), NoticeAwareKernel(),
                         {"r": jnp.float32(2.0)}, k=10.0, n_events=100,
                         key=KEY, hazards=jnp.float32([-1.0, 0.5]))
    with pytest.raises(ValueError, match="non-finite"):
        run_region_sweep(_topo(), RoutingKernel(
            base=NoticeAwareKernel(), choice="cheapest"),
            {"r": jnp.float32(2.0)}, k=10.0, n_events=100, key=KEY,
            prices=jnp.float32([np.inf, 1.0]))
    # scalar broadcast stays legal (fills every pool)
    out = run_market_sweep(Exponential(1.2), _market(), NoticeAwareKernel(),
                           {"r": jnp.float32(2.0)}, k=10.0, n_events=500,
                           key=KEY, hazards=0.05)
    assert np.isfinite(out["avg_cost"]).all()


def test_timeline_validation():
    with pytest.raises(ValueError, match="increasing"):
        EnvTimeline(t_end=(5.0, 2.0, float("inf")))
    with pytest.raises(ValueError, match="open-ended"):
        EnvTimeline(t_end=(5.0, 10.0))
    with pytest.raises(ValueError, match="hazard_mult"):
        inject_storm(EnvTimeline.constant(), 1.0, 2.0, hazard_mult=0.0)
    with pytest.raises(ValueError, match="price_mult"):
        inject_price_spike(EnvTimeline.constant(), 1.0, 2.0, price_mult=-1.0)


def test_non_finite_stats_raise():
    good = SimpleNamespace(cost_sum=np.float64(1.0),
                           delay_sum=np.float64(2.0),
                           time_elapsed=np.float64(3.0))
    _check_finite_stats(good)
    bad = SimpleNamespace(cost_sum=np.float64(np.nan),
                          delay_sum=np.float64(2.0),
                          time_elapsed=np.float64(3.0))
    with pytest.raises(NonFiniteStatsError, match="cost_sum"):
        _check_finite_stats(bad)
