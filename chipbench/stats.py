"""Check arithmetic shared by the configurations' references.

Every number here is computed from the answers that the timed calls
returned, as plain float64 numpy; nothing of the program under test is
imported.  Statistics are in units of the seed spread: lanes that share a
seed share their random streams across the policy grid (common random
numbers), so the seed, not the lane, is the independent sample.
"""
from __future__ import annotations

import numpy as np


def stack(answers: list[dict], name: str) -> np.ndarray:
    """``name`` of every answer joined along the seed axis (axis 1):
    ``(grid_points, answers * seeds, ...)``."""
    return np.concatenate([np.asarray(a[name], np.float64) for a in answers],
                          axis=1)


def seed_z(a, b=None) -> float:
    """Worst |mean(a - b)| over the seed axis, in standard errors of that
    difference's seed spread (``chip_smoke.seed_z``, with the seed axis
    second and any further axes kept apart)."""
    d = np.asarray(a, np.float64) - (0.0 if b is None
                                     else np.asarray(b, np.float64))
    d = np.moveaxis(d, 1, -1)
    se = d.std(axis=-1, ddof=1) / np.sqrt(d.shape[-1])
    mean = d.mean(axis=-1)
    z = np.where(se > 0, np.abs(mean) / np.maximum(se, 1e-300),
                 np.where(mean == 0, 0.0, np.inf))
    return float(np.max(z))


def per_seed(x) -> np.ndarray:
    """Sum over the grid axis: one value per seed, ``(1, seeds, ...)``."""
    return np.asarray(x, np.float64).sum(axis=0, keepdims=True)


def horizon_z(time, n_events: int, total_rate: float) -> float:
    """Simulated hours against the event budget.

    Every event of these loops is the tick of one of several independent
    Poisson clocks whose rates sum to ``total_rate``, whatever the policy
    does, so a lane's simulated time over ``n_events`` events is a sum of
    ``n_events`` exponentials of that rate: mean ``n_events / total_rate``.
    A lane that simulates fewer events than it reports, or clocks at other
    rates than the deployment states, reads far off.
    """
    t = np.asarray(time, np.float64)
    return seed_z(per_seed(t), per_seed(np.full(t.shape, n_events / total_rate)))


def count_z(counts, expected) -> float:
    """Counts ``(grid, seeds, ...)`` against their exact expectations
    ``(grid, ...)``: the worst :func:`seed_z` over the cells whose
    expectation is above 0; a cell whose expectation is exactly 0 (a pool
    the rule never uses) reads ``inf`` unless every count there is 0."""
    c = np.moveaxis(np.asarray(counts, np.float64), 1, -1)
    e = np.asarray(expected, np.float64)
    never = e == 0
    if np.any(c[never] != 0):
        return float("inf")
    if np.all(never):
        return 0.0
    return seed_z(c[~never], e[~never][:, None])


#: The statistics that identify a simulated lane: its simulated hours
#: and arrivals depend on the seed alone (the same at every grid point),
#: its completions and on-demand jobs on the seed and the policy.
LANE_ROW = ("time", "jobs_arrived", "jobs_completed", "ondemand")


def repeats(answers: list[dict]) -> int:
    """Lanes whose ``LANE_ROW`` equals another seed's at the same grid
    point, plus answers whose rows all equal the previous answer's.  Every
    call draws fresh keys and every seed its own stream, so any repeat is
    a lane or an answer that was not simulated.  A single statistic would
    not do: two seeds whose float32 hours happen to be equal are equal at
    every grid point."""
    n = 0
    prev = None
    for a in answers:
        x = np.concatenate([np.asarray(a[name], np.float64).reshape(
            *np.shape(a[name])[:2], -1) for name in LANE_ROW], axis=-1)
        for row in x:  # one grid point: (seeds, statistics)
            n += row.shape[0] - np.unique(row, axis=0).shape[0]
        if prev is not None and prev.shape == x.shape and np.array_equal(prev, x):
            n += 1
        prev = x
    return int(n)


def well_formed(answer: dict, names, shape: tuple) -> bool:
    """Every statistic in ``names`` present, of the grid's shape, finite."""
    for name in names:
        x = answer.get(name)
        if (x is None or np.shape(x)[:2] != shape
                or not np.all(np.isfinite(np.asarray(x, np.float64)))):
            return False
    return True
