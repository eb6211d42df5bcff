"""The one traffic generator: a mix's parameters become entry-point calls.

A traffic mix is a data file (``chipbench/traffic/<mix>.json``) of
parameters: which entry of the configuration's program a client calls
(``entry``), the policy grid (``r``), seeds per grid point, events and
burn-in per lane.  Every
seed gives the same sizes; the seed only picks the random streams.
"""
from __future__ import annotations

import numpy as np


def r_grid(spec: dict) -> np.ndarray:
    """``linspace: [start, stop, num]``, joined with the values in ``with``."""
    rs = np.linspace(*spec["linspace"])
    if "with" in spec:
        rs = np.union1d(rs, np.asarray(spec["with"], np.float64))
    return rs.astype(np.float32)


def seed_words(seed: int) -> np.ndarray:
    """Two uint32 words of key data from a seed of any size."""
    return np.random.SeedSequence(seed).generate_state(2, np.uint32)


class Load:
    """One client's calls: call ``i`` gets the key (seed, i)."""

    def __init__(self, program, traffic: dict, seed: int):
        import jax
        import jax.numpy as jnp
        self.rs = r_grid(traffic["r"])
        self.n_seeds = int(traffic["n_seeds"])
        self.n_events = int(traffic["n_events"])
        self.burn_in = int(traffic["burn_in"])
        self.lane_events = self.rs.size * self.n_seeds * (self.n_events
                                                          + self.burn_in)
        self._entry = getattr(program, traffic["entry"])
        self._base = jax.random.wrap_key_data(jnp.asarray(seed_words(seed)))

    def call(self, i: int) -> dict:
        """One whole call; returns when its answer is on the host."""
        import jax
        return self._entry(self.rs, jax.random.fold_in(self._base, i),
                           n_seeds=self.n_seeds, n_events=self.n_events,
                           burn_in=self.burn_in)
