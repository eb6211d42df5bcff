"""Plain reference for ``fig2_single_queue``: what its answers must say.

The closed forms are copies of ``repro.core.analytic.theorem5_cost`` and
``repro.core.cost.theorem1_cost`` (the paper's Theorems 5 and 1), kept
here so that no change to the program moves the yardstick; a test holds
the copies equal to the originals.  Nothing of the program is imported.
"""
from __future__ import annotations

import numpy as np

from chipbench import stats

STATS = ("jobs_arrived", "jobs_completed", "spot_served", "ondemand",
         "avg_cost", "pi0_spot", "time")


def theorem5_cost(k: float, lam: float, mu: float, n_max: int) -> float:
    """E[C_N] = k - (k-1)(mu/lam)(1 - (lam/mu - 1)/((lam/mu)^(N+1) - 1))."""
    rho = lam / mu
    if abs(rho - 1.0) < 1e-12:
        util = n_max / (n_max + 1.0)
    else:
        util = 1.0 - (rho - 1.0) / (rho ** (n_max + 1) - 1.0)
    return k - (k - 1.0) * (mu / lam) * util


def theorem1_cost(k: float, lam: float, mu: float, pi0):
    """E[C] from the probability that a spot arrival finds the queue empty."""
    return k - (k - 1.0) * (mu / lam) * (1.0 - pi0)


def compare(cfg: dict, traffic: dict, rs: np.ndarray,
            answers: list[dict]) -> dict:
    """The numbers compared, by name; ``cfg["limits"]`` holds their limits."""
    lam, mu, k = cfg["job_rate"], cfg["spot_rate"], cfg["k"]
    shape = (rs.size, traffic["n_seeds"])
    good = [a for a in answers if stats.well_formed(a, STATS, shape)]
    out = {"malformed": len(answers) - len(good)}
    if not good:
        return {**out, **{n: float("inf") for n in cfg["limits"]
                          if n != "malformed"}}
    out["repeats"] = stats.repeats(good)
    get = lambda name: stats.stack(good, name)
    completed = get("jobs_completed")
    closed = get("spot_served") + get("ondemand")
    backlog = np.abs(get("jobs_arrived") - completed) - cfg["rmax"]
    out["ledger_gap"] = float(max(np.max(np.abs(completed - closed)),
                                  np.max(backlog), 0.0))
    time = get("time")
    out["horizon_z"] = stats.horizon_z(time, traffic["n_events"], lam + mu)
    cost = get("avg_cost")
    ints = np.flatnonzero(rs == np.round(rs))
    closed_form = np.array([theorem5_cost(k, lam, mu, int(rs[i]))
                            for i in ints])
    out["thm5_z"] = (stats.seed_z(cost[ints], closed_form[:, None])
                     if ints.size else 0.0)
    out["thm1_z"] = stats.seed_z(cost, theorem1_cost(k, lam, mu,
                                                     get("pi0_spot")))
    return out
