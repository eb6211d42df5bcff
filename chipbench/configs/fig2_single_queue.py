"""``fig2_single_queue`` on the program: the deployment's objects and the
entry point that a sweep of it calls."""
from __future__ import annotations


def control(cfg: dict) -> dict:
    """The configuration with its stated guarantee broken (``control``):
    what the control runs in the program's place."""
    return {**cfg, "spot_rate": cfg["spot_rate"]
            * cfg["control"]["spot_rate_scale"]}


class Program:
    """Built once in set-up; each call is one ``run_sweep`` of the grid."""

    def __init__(self, cfg: dict):
        from repro.core import Exponential, ThreePhaseKernel
        if cfg["kernel"] != "ThreePhaseKernel":
            raise ValueError(f"unknown kernel {cfg['kernel']!r}")
        self.cfg = cfg
        self.job = Exponential(cfg["job_rate"])
        self.spot = Exponential(cfg["spot_rate"])
        self.kernel = ThreePhaseKernel()

    def sweep(self, rs, key, *, n_seeds: int, n_events: int,
              burn_in: int) -> dict:
        from repro.core import run_sweep
        return run_sweep(self.job, self.spot, self.kernel, {"r": rs},
                         k=self.cfg["k"], n_events=n_events, key=key,
                         n_seeds=n_seeds, rmax=self.cfg["rmax"],
                         burn_in=burn_in, **self.cfg["executor"])
