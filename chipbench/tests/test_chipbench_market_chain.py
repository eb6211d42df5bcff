"""The market's plain reference: the chain's exact window counts agree
with a direct event-by-event simulation of the same semantics, its
admission law is the program's, and a count that the rule makes
impossible fails the comparison."""
from __future__ import annotations

import json

import numpy as np
import pytest

from chipbench_testlib import ROOT

from chipbench import market_chain, stats

CONFIG = json.loads((ROOT / "chipbench" / "configs"
                     / "market4_diversified.json").read_text())


def _busy(rule: str) -> dict:
    """The diversified market with hazards that revoke often, one pool
    whose notice is too short for the checkpoint, and ``rule``."""
    pools = [{**p, "hazard": h, "notice": n} for p, h, n in zip(
        CONFIG["pools"], (0.02, 0.01, 0.03, 0.0), (0.5, 0.01, 2.0, 0.5))]
    return {**CONFIG, "pools": pools, "pool_choice": rule,
            "checkpoint_hours": 0.05}


def _simulate(cfg: dict, r: float, n_events: int, burn_in: int,
              lanes: int, seed: int) -> dict:
    """The semantics in the chain's docstring, drawn event by event over
    ``lanes`` independent lanes: per-lane counts of the window."""
    rng = np.random.default_rng(seed)
    pools = cfg["pools"]
    n_p = len(pools)
    rate = np.array([p["spot_rate"] for p in pools])
    hazard = np.array([p["hazard"] for p in pools])
    price = np.array([p["price"] for p in pools])
    fits = np.array([cfg["checkpoint_hours"] <= p["notice"] for p in pools])
    weights = np.concatenate([[cfg["job_rate"]], rate, hazard])
    edges = np.cumsum(weights / weights.sum())
    n = np.zeros((lanes, n_p), np.int64)
    out = {name: np.zeros(lanes) for name in ("ondemand", "resumed",
                                              "cost_sum")}
    out.update(pool_served=np.zeros((lanes, n_p)),
               pool_preempted=np.zeros((lanes, n_p)))
    lane = np.arange(lanes)
    n_hat, frac = np.floor(r), r - np.floor(r)

    def admit(q):
        return np.where(q < n_hat, 1.0, np.where(q == n_hat, frac, 0.0))

    for t in range(burn_in + n_events):
        keep = 1.0 if t >= burn_in else 0.0
        kind = np.minimum(np.searchsorted(edges, rng.random(lanes),
                                          side="right"), 2 * n_p)
        u = rng.random(lanes)
        q = n.sum(axis=1)
        job = kind == 0
        taken = job & (u < admit(q)) & (q < cfg["rmax"])
        if cfg["pool_choice"] == "cheapest":
            pick = np.full(lanes, int(np.argmin(price)))
        else:
            pick = np.argmin(n, axis=1)
        n[lane[taken], pick[taken]] += 1
        refused = job & ~taken
        out["ondemand"] += keep * refused
        out["cost_sum"] += keep * refused * cfg["k"]
        pool = (kind - 1) % n_p
        has = n[lane, pool] > 0
        slot = (kind >= 1) & (kind <= n_p) & has
        rev = (kind > n_p) & has
        back = rev & fits[pool] & (u < admit(q - 1))
        gone = slot | (rev & ~back)
        n[lane[gone], pool[gone]] -= 1
        out["pool_served"][lane[slot], pool[slot]] += keep
        out["pool_preempted"][lane[rev], pool[rev]] += keep
        out["resumed"] += keep * back
        out["ondemand"] += keep * (rev & ~back)
        out["cost_sum"] += keep * ((slot | rev) * price[pool]
                                   + (rev & ~back) * cfg["k"])
    return out


@pytest.mark.parametrize("rule,r,burn_in", [("least_loaded", 3.4, 0),
                                            ("least_loaded", 2.0, 300),
                                            ("cheapest", 1.6, 0)])
def test_the_chain_agrees_with_a_direct_simulation(rule, r, burn_in):
    cfg = _busy(rule)
    want = market_chain.window_counts(cfg, r, 1500, burn_in)
    got = _simulate(cfg, r, 1500, burn_in, lanes=3000, seed=17)
    for name in market_chain.COUNTS:
        x = got[name].reshape(1, got[name].shape[0], -1)
        z = stats.count_z(x, np.reshape(want[name], (1, -1)))
        assert z < 5.0, (name, z, x.mean(axis=1), want[name])


def test_the_chain_differs_where_the_law_differs():
    """At the cells' windows a shift of the admission level by 0.1 moves
    the expected on-demand count by far more than its seed spread."""
    cfg = _busy("least_loaded")
    a = market_chain.window_counts(cfg, 3.4, 1500, 0)["ondemand"]
    b = market_chain.window_counts(cfg, 3.5, 1500, 0)["ondemand"]
    assert abs(a - b) > 5.0


@pytest.mark.parametrize("r", [0.25, 0.5, 1.0, 2.7, 8.0])
@pytest.mark.parametrize("q", [0, 1, 2, 3, 8, 9])
def test_the_admission_law_is_the_programs(q, r):
    from repro.core.policies import three_phase_admit_prob
    assert market_chain.admit_prob(q, r) == three_phase_admit_prob(q, r)


def test_a_count_where_the_rule_allows_none_fails():
    expected = np.array([[4.0, 0.0]])
    counts = np.array([[[4.0, 0.0], [5.0, 0.0], [3.0, 0.0]]])
    assert stats.count_z(counts, expected) == 0.0
    counts[0, 1, 1] = 1.0
    assert stats.count_z(counts, expected) == float("inf")
