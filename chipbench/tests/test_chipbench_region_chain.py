"""The two-region reference: the chain's exact window counts agree with a
direct event-by-event simulation of the same semantics, a fault planted in
any of the chain's three laws fails that agreement, and the cell
``region2.deep`` reads correct at a tiny size on the CPU while its control,
each timed-path fault and each broken law of the program do not."""
from __future__ import annotations

import json

import numpy as np
import pytest

from chipbench_testlib import ROOT, run_tiny, tiny_root
from test_chipbench_checks import FAULTS, _break

from chipbench import market_chain, region_chain, stats

CELL = "region2.deep"
CONFIG = json.loads((ROOT / "chipbench" / "configs"
                     / "region2_least_loaded.json").read_text())


def _busy() -> dict:
    """The configuration with hazards that revoke often and a second
    region whose notice is too short for the checkpoint."""
    regions = [{**g, "hazard": h, "notice": n} for g, h, n in zip(
        CONFIG["regions"], (0.03, 0.01), (CONFIG["regions"][0]["notice"],
                                          0.01))]
    return {**CONFIG, "regions": regions}


def _simulate(cfg: dict, r: float, n_events: int, burn_in: int,
              lanes: int, seed: int) -> dict:
    """The semantics in the chain's docstring, drawn event by event over
    ``lanes`` independent lanes: per-lane counts of the window."""
    rng = np.random.default_rng(seed)
    regions = cfg["regions"]
    n_r = len(regions)
    lam = np.array([g["job_rate"] for g in regions])
    rate = np.array([g["spot_rate"] for g in regions])
    hazard = np.array([g["hazard"] for g in regions])
    price = np.array([g["price"] for g in regions])
    rmax = np.array([g["rmax"] for g in regions])
    fits = np.array([cfg["checkpoint_hours"] <= g["notice"]
                     for g in regions])
    weights = np.concatenate([lam, rate, hazard])
    edges = np.cumsum(weights / weights.sum())
    n = np.zeros((lanes, n_r), np.int64)
    out = {name: np.zeros(lanes) for name in ("ondemand", "resumed",
                                              "cost_sum", "routed_home")}
    out.update({name: np.zeros((lanes, n_r)) for name in (
        "region_served", "region_preempted", "region_routed")})
    lane = np.arange(lanes)
    n_hat, frac = np.floor(r), r - np.floor(r)

    def law(q):
        return np.where(q < n_hat, 1.0, np.where(q == n_hat, frac, 0.0))

    for t in range(burn_in + n_events):
        keep = 1.0 if t >= burn_in else 0.0
        kind = np.minimum(np.searchsorted(edges, rng.random(lanes),
                                          side="right"), 3 * n_r - 1)
        u = rng.random(lanes)
        job = kind < n_r
        target = np.argmin(n, axis=1)
        q_t = n[lane, target]
        taken = job & (u < law(q_t)) & (q_t < rmax[target])
        n[lane[taken], target[taken]] += 1
        out["region_routed"][lane[taken], target[taken]] += keep
        out["routed_home"] += keep * (taken & (target == kind))
        refused = job & ~taken
        out["ondemand"] += keep * refused
        out["cost_sum"] += keep * refused * cfg["k"]
        reg = kind % n_r
        q_reg = n[lane, reg]
        slot = (kind >= n_r) & (kind < 2 * n_r) & (q_reg > 0)
        rev = (kind >= 2 * n_r) & (q_reg > 0)
        back = rev & fits[reg] & (u < law(q_reg - 1))
        gone = slot | (rev & ~back)
        n[lane[gone], reg[gone]] -= 1
        out["region_served"][lane[slot], reg[slot]] += keep
        out["region_preempted"][lane[rev], reg[rev]] += keep
        out["resumed"] += keep * back
        out["ondemand"] += keep * (rev & ~back)
        out["cost_sum"] += keep * ((slot | rev) * price[reg]
                                   + (rev & ~back) * cfg["k"])
    return out


def _worst_z(r: float, burn_in: int) -> dict:
    """Each count of a simulated window against the chain's expectation,
    in standard errors of the lane spread."""
    cfg = _busy()
    want = region_chain.window_counts(cfg, r, 1500, burn_in)
    got = _simulate(cfg, r, 1500, burn_in, lanes=3000, seed=23)
    out = {}
    for name in region_chain.COUNTS:
        x = got[name].reshape(1, got[name].shape[0], -1)
        out[name] = stats.count_z(x, np.reshape(want[name], (1, -1)))
    return out


#: Small r where every law shows: a queue of two in a region (r = 1.5
#: admits at one, by half) and a fractional re-admission.
POINTS = [(1.5, 0), (2.6, 300), (0.7, 0)]


@pytest.mark.parametrize("r,burn_in", POINTS)
def test_the_chain_agrees_with_a_direct_simulation(r, burn_in):
    z = _worst_z(r, burn_in)
    assert max(z.values()) < 5.0, z


def _busier(rule, n):
    """Routing broken: the region holding most jobs."""
    return int(np.argmax(n))


def _total_queue(n, target, r, rmax):
    """Admission broken: the law at the total of the queues."""
    return market_chain.admit_prob(sum(n), r) if n[target] < rmax else 0.0


def _no_readmission(n, region, r, fits):
    """Resumption broken: every job whose checkpoint fits resumes."""
    return 1.0 if fits else 0.0


CHAIN_FAULTS = {"route": _busier, "admit": _total_queue,
                "resume": _no_readmission}


@pytest.mark.parametrize("fault", sorted(CHAIN_FAULTS))
def test_a_planted_chain_fault_fails_the_agreement(monkeypatch, fault):
    monkeypatch.setattr(region_chain, fault, CHAIN_FAULTS[fault])
    worst = [max(_worst_z(r, b).values()) for r, b in POINTS]
    assert max(worst) > 20.0, worst


def test_the_chain_is_small_and_routes_half_away():
    """At r = 8 the chain has at most 81 states; least-loaded routing of
    two even regions sends about half of the admitted jobs away from
    their home region."""
    P, _ = region_chain._chain(CONFIG, 8.0)
    assert P.shape[0] <= 81
    np.testing.assert_allclose(P.sum(axis=1), 1.0, rtol=1e-12)
    w = region_chain.window_counts(CONFIG, 8.0, 10_000, 1_000)
    cross = 1.0 - w["routed_home"] / w["region_routed"].sum()
    assert 0.4 < cross < 0.6, cross


# ------------------------------------------- the cell, tiny, on the CPU
@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("region2"))


def test_the_cell_reads_correct_and_its_control_does_not(tiny):
    sound = run_tiny(tiny, CELL, seed=2**33 + 7)
    control = run_tiny(tiny, CELL, seed=2**33 + 7, control=True)
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] == 2 and sound["failed"] == 0
    assert not control["correct"], control["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_cells_timed_path_reads_not_correct(
        tiny, monkeypatch, fault):
    _break(monkeypatch, FAULTS[fault])
    line = run_tiny(tiny, CELL)
    assert line["attempted"] == 2
    assert not line["correct"], line["checks"]


@pytest.fixture
def fresh_programs():
    """Programs traced before and after a law is broken are not reused."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def _route_busier(monkeypatch):
    """The program's routing broken: least-loaded picks the busiest."""
    import jax.numpy as jnp

    from repro.core import regions
    choose = regions.choose_region

    def busiest(choice, view, params, key):
        if choice == "least_loaded":
            return jnp.argmax(view.qlen_region).astype(jnp.int32)
        return choose(choice, view, params, key)
    monkeypatch.setattr(regions, "choose_region", busiest)


def _never_fits(monkeypatch):
    """The program's notice law broken: no checkpoint fits any notice."""
    from repro.core import market
    monkeypatch.setattr(market, "checkpoint_within_notice",
                        lambda checkpoint_time, notice: notice < 0)


@pytest.mark.parametrize("law,number", [("routing", "routed_z"),
                                        ("notice", "resumed_z")])
def test_a_broken_law_of_the_program_reads_not_correct(
        tiny, monkeypatch, fresh_programs, law, number):
    {"routing": _route_busier, "notice": _never_fits}[law](monkeypatch)
    line = run_tiny(tiny, CELL)
    assert not line["correct"], line["checks"]
    assert line["checks"][number]["value"] > line["checks"][number]["limit"]
