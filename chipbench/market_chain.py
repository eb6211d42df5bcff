"""Plain reference of the spot-market semantics: the exact expected counts
of a window, from the Markov chain that the market's event loop is.

The deployment (see ``configs/market4_*.json``): jobs arrive at
``job_rate``; pool ``p`` offers spot slots at ``spot_rate`` and revokes at
``hazard``, both Poisson; a job is admitted by the three-phase law at the
queue length and tagged to a pool by the configuration's rule; a pool's
slot serves its oldest job (cost ``price``); a revocation hits its oldest
job, whose leg is paid (``price``), and the job resumes in place when its
checkpoint fits the pool's notice and the admission law re-admits it at
the queue without it, else it goes on demand (cost ``k``); a job refused
at arrival goes on demand.  No job has a wait budget.

So every count depends on the queue only through the number of jobs
tagged to each pool (ages and FIFO order move delays, not counts), and
every event is a tick of one superposed clock of total rate
``Lambda = job_rate + sum(spot_rate) + sum(hazard)`` whatever the state: a
job with probability ``job_rate / Lambda``, a slot of pool ``p`` with
``spot_rate[p] / Lambda``, a revocation of pool ``p`` with
``hazard[p] / Lambda``.  The per-pool counts after each event are thus a
Markov chain with one transition matrix ``P``, and the expected count of
anything over events ``b .. b+N-1`` from the empty queue is exactly
``sum_t e P^t R``: ``N pi R + (d_b - d_{b+N}) Z R``, with ``pi`` the
stationary law, ``d_t = e P^t - pi`` and ``Z = (I - P + 1 pi)^-1``.

Nothing of the program is imported.
"""
from __future__ import annotations

import math

import numpy as np

from chipbench import stats

#: Per-event expected counts that a window sums, by answer name; the
#: per-pool ones have a trailing pool axis.
COUNTS = ("ondemand", "resumed", "cost_sum", "pool_served",
          "pool_preempted")
#: Below this, what is left of the start's deviation from ``pi`` adds
#: less than a count of 1e-6 to any window.
_TOL = 1e-12


def admit_prob(q: int, r: float) -> float:
    """The three-phase (Theorem 4) admission law at queue length ``q``."""
    n_hat = math.floor(r)
    if q < n_hat:
        return 1.0
    return r - n_hat if q == n_hat else 0.0


def choose(rule: str, n: tuple, price) -> int:
    """The pool an admitted job is tagged to: the cheapest, or the one
    holding fewest jobs (the first of equals, for either)."""
    if rule == "cheapest":
        return int(np.argmin(price))
    if rule == "least_loaded":
        return int(np.argmin(n))
    raise ValueError(f"no reference for pool choice {rule!r}")


def _chain(cfg: dict, r: float):
    """States reachable from the empty queue, ``P`` and the per-event
    expected counts ``R`` (name -> (S,) or (S, pools))."""
    pools = cfg["pools"]
    n_pools = len(pools)
    lam, k = cfg["job_rate"], cfg["k"]
    rate = np.array([p["spot_rate"] for p in pools], np.float64)
    hazard = np.array([p["hazard"] for p in pools], np.float64)
    price = np.array([p["price"] for p in pools], np.float64)
    fits = [cfg["checkpoint_hours"] <= p["notice"] for p in pools]
    total = lam + rate.sum() + hazard.sum()
    rmax = cfg["rmax"]

    def moves(n):
        """(probability, next state, counts) of one event from ``n``."""
        q = sum(n)
        a = admit_prob(q, r) if q < rmax else 0.0
        m = list(n)
        m[choose(cfg["pool_choice"], n, price)] += 1
        out = [((1 - a) * lam / total, n, {"ondemand": 1.0, "cost_sum": k}),
               (a * lam / total, tuple(m), {})]
        for p in range(n_pools):
            w_slot, w_rev = rate[p] / total, hazard[p] / total
            if n[p] == 0:
                out.append((w_slot + w_rev, n, {}))
                continue
            m = list(n)
            m[p] -= 1
            m = tuple(m)
            out.append((w_slot, m, {"cost_sum": price[p],
                                    ("pool_served", p): 1.0}))
            back = admit_prob(q - 1, r) if fits[p] else 0.0
            leg = {"cost_sum": price[p], ("pool_preempted", p): 1.0}
            out.append((w_rev * back, n, {**leg, "resumed": 1.0}))
            out.append((w_rev * (1 - back), m,
                        {**leg, "ondemand": 1.0, "cost_sum": price[p] + k}))
        return out

    start = (0,) * n_pools
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
    R = {name: np.zeros(size) for name in ("ondemand", "resumed", "cost_sum")}
    R.update({name: np.zeros((size, n_pools))
              for name in ("pool_served", "pool_preempted")})
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


def _deviation(d: np.ndarray, P: np.ndarray, steps: int) -> np.ndarray:
    """``d P^steps``, cut to 0 once it is below :data:`_TOL`."""
    for _ in range(steps):
        if np.abs(d).sum() < _TOL:
            return np.zeros_like(d)
        d = d @ P
    return d


def window_counts(cfg: dict, r: float, n_events: int,
                  burn_in: int) -> dict:
    """Exact expected counts of one lane's window at admission level
    ``r``: ``n_events`` events after ``burn_in``, from the empty queue."""
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
         "resumed", "avg_cost", "time", "pool_spot_arrivals", "pool_served",
         "pool_preempted")
#: The compared number of each count.
Z_NAMES = {"ondemand": "ondemand_z", "resumed": "resumed_z",
           "cost_sum": "cost_z", "pool_served": "served_z",
           "pool_preempted": "preempted_z"}


def compare(cfg: dict, traffic: dict, rs: np.ndarray,
            answers: list[dict]) -> dict:
    """The numbers compared, by name; ``cfg["limits"]`` holds their limits.

    Each ``_z`` number holds a count of every answer, per grid point (and
    pool), to its exact expectation over the window, in standard errors
    of the seed spread; the exact ones hold the leg ledger."""
    rate = np.array([p["spot_rate"] for p in cfg["pools"]])
    hazard = np.array([p["hazard"] for p in cfg["pools"]])
    shape = (rs.size, traffic["n_seeds"])
    good = [a for a in answers if stats.well_formed(a, STATS, shape)]
    out = {"malformed": len(answers) - len(good)}
    if not good:
        return {**out, **{n: float("inf") for n in cfg["limits"]
                          if n != "malformed"}}
    out["repeats"] = stats.repeats(good)
    get = lambda name: stats.stack(good, name)
    spot, ondemand = get("spot_served"), get("ondemand")
    completed = get("jobs_completed")
    gaps = [np.abs(completed - (spot + ondemand + get("resumed"))),
            np.abs(get("pool_served").sum(axis=-1) - spot),
            np.abs(get("jobs_arrived") - (spot + ondemand)) - cfg["rmax"]]
    out["ledger_gap"] = float(max(max(np.max(g) for g in gaps), 0.0))
    out["horizon_z"] = stats.horizon_z(
        get("time"), traffic["n_events"],
        cfg["job_rate"] + rate.sum() + hazard.sum())
    want = all_windows(cfg, rs, int(traffic["n_events"]),
                       int(traffic["burn_in"]))
    got = {name: get(name) for name in COUNTS if name != "cost_sum"}
    got["cost_sum"] = get("avg_cost") * completed
    for name, z in Z_NAMES.items():
        out[z] = stats.count_z(got[name], want[name])
    return out
