"""Plain reference of the multi-region semantics: the exact expected counts
of a window, from the Markov chain that the region event loop is.

The deployment (see ``configs/region2_*.json``): jobs arrive in region
``h`` at its ``job_rate``; region ``p`` offers spot slots at its
``spot_rate`` and revokes at its ``hazard``, all Poisson.  An arriving job
is sent to the region holding fewest jobs (``routing`` ``least_loaded``,
the first of equals) and admitted by the three-phase law at *that
region's* queue length (and only while it holds fewer than its
``rmax``); a refused job goes on demand (cost ``k``).
A slot of region ``p`` serves ``p``'s oldest job (cost ``price``).  A
revocation in ``p`` hits its oldest job, whose leg is paid (``price``);
the job resumes in place when its checkpoint fits ``p``'s notice and the
law re-admits it at ``p``'s queue without it, else it goes on demand.  No
job has a wait budget.

So every count depends on the queues only through the number of jobs in
each region, and every event is a tick of one superposed clock of total
rate ``Lambda = sum(job_rate) + sum(spot_rate) + sum(hazard)`` whatever
the state.  The per-region counts after each event are a Markov chain
(at most 81 states for two regions at r = 8), and a window's expected
counts are ``N pi R + (d_b - d_{b+N}) Z R``: the window arithmetic of
:mod:`chipbench.market_chain`, imported from there.

Nothing of the program is imported.
"""
from __future__ import annotations

import numpy as np

from chipbench import stats
from chipbench.market_chain import _deviation, admit_prob

#: Per-event expected counts that a window sums, by answer name; the
#: per-region ones have a trailing region axis.
COUNTS = ("ondemand", "resumed", "cost_sum", "routed_home",
          "region_served", "region_preempted", "region_routed")
_PER_REGION = ("region_served", "region_preempted", "region_routed")


def route(rule: str, n: tuple) -> int:
    """The region an arriving job is sent to: the one holding fewest jobs,
    the first of equals."""
    if rule != "least_loaded":
        raise ValueError(f"no reference for routing rule {rule!r}")
    return int(np.argmin(n))


def admit(n: tuple, target: int, r: float, rmax: int) -> float:
    """The chance that a job sent to ``target`` is admitted: the law at
    that region's queue length, while it has room."""
    return admit_prob(n[target], r) if n[target] < rmax else 0.0


def resume(n: tuple, region: int, r: float, fits: bool) -> float:
    """The chance that a job revoked in ``region`` resumes in place: its
    checkpoint fits the notice and the law re-admits it at the region's
    queue without it."""
    return admit_prob(n[region] - 1, r) if fits else 0.0


def _chain(cfg: dict, r: float):
    """States reachable from the empty queues, ``P`` and the per-event
    expected counts ``R`` (name -> (S,) or (S, regions))."""
    regions = cfg["regions"]
    n_reg = len(regions)
    k = cfg["k"]
    lam = np.array([g["job_rate"] for g in regions], np.float64)
    rate = np.array([g["spot_rate"] for g in regions], np.float64)
    hazard = np.array([g["hazard"] for g in regions], np.float64)
    price = np.array([g["price"] for g in regions], np.float64)
    rmax = [g["rmax"] for g in regions]
    fits = [cfg["checkpoint_hours"] <= g["notice"] for g in regions]
    total = lam.sum() + rate.sum() + hazard.sum()

    def moves(n):
        """(probability, next state, counts) of one event from ``n``."""
        out = []
        t = route(cfg["routing"], n)
        a = admit(n, t, r, rmax[t])
        m = list(n)
        m[t] += 1
        for h in range(n_reg):
            out.append(((1 - a) * lam[h] / total, n,
                        {"ondemand": 1.0, "cost_sum": k}))
            out.append((a * lam[h] / total, tuple(m),
                        {("region_routed", t): 1.0,
                         "routed_home": float(t == h)}))
        for p in range(n_reg):
            w_slot, w_rev = rate[p] / total, hazard[p] / total
            if n[p] == 0:
                out.append((w_slot + w_rev, n, {}))
                continue
            m = list(n)
            m[p] -= 1
            m = tuple(m)
            out.append((w_slot, m, {"cost_sum": price[p],
                                    ("region_served", p): 1.0}))
            back = resume(n, p, r, fits[p])
            leg = {"cost_sum": price[p], ("region_preempted", p): 1.0}
            out.append((w_rev * back, n, {**leg, "resumed": 1.0}))
            out.append((w_rev * (1 - back), m,
                        {**leg, "ondemand": 1.0, "cost_sum": price[p] + k}))
        return out

    start = (0,) * n_reg
    index, order, frontier = {start: 0}, [start], [start]
    table = {}
    while frontier:
        n = frontier.pop()
        table[n] = moves(n)
        for w, m, _ in table[n]:
            if w > 0 and m not in index:
                index[m] = len(order)
                order.append(m)
                frontier.append(m)
    size = len(order)
    P = np.zeros((size, size))
    R = {name: (np.zeros((size, n_reg)) if name in _PER_REGION
                else np.zeros(size)) for name in COUNTS}
    for n, i in index.items():
        for w, m, counts in table[n]:
            if w <= 0:
                continue
            P[i, index[m]] += w
            for name, c in counts.items():
                if isinstance(name, tuple):
                    R[name[0]][i, name[1]] += w * c
                else:
                    R[name][i] += w * c
    return P, R


def window_counts(cfg: dict, r: float, n_events: int,
                  burn_in: int) -> dict:
    """Exact expected counts of one lane's window at admission level
    ``r``: ``n_events`` events after ``burn_in``, from the empty queues
    (``market_chain.window_counts``' arithmetic on this chain)."""
    P, R = _chain(cfg, float(r))
    size = P.shape[0]
    A = P.T - np.eye(size)
    A[-1] = 1.0
    b = np.zeros(size)
    b[-1] = 1.0
    pi = np.linalg.solve(A, b)
    e = np.zeros(size)
    e[0] = 1.0
    d_b = _deviation(e - pi, P, burn_in)
    d_end = _deviation(d_b, P, n_events)
    fundamental = np.eye(size) - P + pi[None, :]
    out = {}
    for name, rew in R.items():
        zr = np.linalg.solve(fundamental, rew)
        out[name] = n_events * (pi @ rew) + (d_b - d_end) @ zr
    return out


def all_windows(cfg: dict, rs, n_events: int, burn_in: int) -> dict:
    """:func:`window_counts` over the grid: name -> (grid, ...)."""
    per_r = [window_counts(cfg, r, n_events, burn_in) for r in rs]
    return {name: np.stack([w[name] for w in per_r]) for name in COUNTS}


#: What each answer must hold, every one of the grid's shape and finite.
STATS = ("jobs_arrived", "jobs_completed", "spot_served", "ondemand",
         "resumed", "avg_cost", "time", "routed_home", "region_served",
         "region_preempted", "region_jobs", "region_routed")
#: The compared number of each count; ``routed_z`` holds two.
Z_NAMES = {"ondemand": "ondemand_z", "resumed": "resumed_z",
           "cost_sum": "cost_z", "region_served": "served_z",
           "region_preempted": "preempted_z", "region_routed": "routed_z",
           "routed_home": "routed_z"}


def compare(cfg: dict, traffic: dict, rs: np.ndarray,
            answers: list[dict]) -> dict:
    """The numbers compared, by name; ``cfg["limits"]`` holds their limits.

    Each ``_z`` number holds a count of every answer, per grid point (and
    region), to its exact expectation over the window, in standard errors
    of the seed spread; the exact ones hold the leg ledger."""
    regions = cfg["regions"]
    shape = (rs.size, traffic["n_seeds"])
    good = [a for a in answers if stats.well_formed(a, STATS, shape)]
    out = {"malformed": len(answers) - len(good)}
    if not good:
        return {**out, **{n: float("inf") for n in cfg["limits"]
                          if n != "malformed"}}
    out["repeats"] = stats.repeats(good)
    get = lambda name: stats.stack(good, name)
    spot, ondemand = get("spot_served"), get("ondemand")
    completed, arrived = get("jobs_completed"), get("jobs_arrived")
    gaps = [np.abs(completed - (spot + ondemand + get("resumed"))),
            np.abs(get("region_served").sum(axis=-1) - spot),
            np.abs(get("region_jobs").sum(axis=-1) - arrived),
            np.abs(arrived - (spot + ondemand))
            - sum(g["rmax"] for g in regions)]
    out["ledger_gap"] = float(max(max(np.max(g) for g in gaps), 0.0))
    out["horizon_z"] = stats.horizon_z(
        get("time"), traffic["n_events"],
        sum(g["job_rate"] + g["spot_rate"] + g["hazard"] for g in regions))
    want = all_windows(cfg, rs, int(traffic["n_events"]),
                       int(traffic["burn_in"]))
    got = {name: get(name) for name in COUNTS if name != "cost_sum"}
    got["cost_sum"] = get("avg_cost") * completed
    for name, z in Z_NAMES.items():
        out[z] = max(out.get(z, 0.0), stats.count_z(got[name], want[name]))
    return out
