"""A four-pool market configuration on the program (``configs/market4_*``):
the deployment's objects and the entry points that a sweep of it, or the
controller's what-if, calls."""
from __future__ import annotations


def control(cfg: dict) -> dict:
    """The configuration with its stated guarantee broken (``control``):
    what the control runs in the program's place."""
    scale = cfg["control"]["spot_rate_scale"]
    return {**cfg, "pools": [{**p, "spot_rate": p["spot_rate"] * scale}
                             for p in cfg["pools"]]}


class Program:
    """Built once in set-up; each call is one sweep or one what-if answer."""

    def __init__(self, cfg: dict):
        from repro.cluster.orchestrator import (OnlineAdmissionController,
                                                SpotCluster)
        from repro.core import (Exponential, NoticeAwareKernel, SpotMarket,
                                SpotPool)
        if cfg["kernel"] != "NoticeAwareKernel":
            raise ValueError(f"unknown kernel {cfg['kernel']!r}")
        self.cfg = cfg
        self.job = Exponential(cfg["job_rate"])
        self.market = SpotMarket(pools=tuple(
            SpotPool(Exponential(p["spot_rate"]), price=p["price"],
                     hazard=p["hazard"], notice=p["notice"])
            for p in cfg["pools"]))
        self.kernel = NoticeAwareKernel(checkpoint_time=cfg["checkpoint_hours"],
                                        choice=cfg["pool_choice"])
        # The online controller's own state plays no part in a what-if
        # answer; its delay target is the one fig. 2's policy grid meets.
        self.cluster = SpotCluster(
            job_process=self.job, market=self.market, k_cost=cfg["k"],
            controller=OnlineAdmissionController(delta=12.0),
            checkpoint_hours=cfg["checkpoint_hours"])

    def sweep(self, rs, key, *, n_seeds: int, n_events: int,
              burn_in: int) -> dict:
        from repro.core import run_market_sweep
        return run_market_sweep(self.job, self.market, self.kernel, {"r": rs},
                                k=self.cfg["k"], n_events=n_events, key=key,
                                n_seeds=n_seeds, rmax=self.cfg["rmax"],
                                burn_in=burn_in, **self.cfg["executor"])

    def what_if(self, rs, key, *, n_seeds: int, n_events: int,
                burn_in: int) -> dict:
        # what_if_sweep runs its own executor, the cheapest-pool rule, no
        # burn-in and the engine's rmax
        if (burn_in or self.cfg["pool_choice"] != "cheapest"
                or self.cfg["rmax"] != 64):
            raise ValueError("what_if_sweep answers for the cheapest pool, "
                             "rmax 64 and no burn-in only")
        return self.cluster.what_if_sweep(rs, n_events=n_events,
                                          n_seeds=n_seeds, key=key)
