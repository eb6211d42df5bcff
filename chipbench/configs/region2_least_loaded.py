"""``region2_least_loaded`` on the program: the two-region topology, the
notice-aware policy behind the configuration's routing rule, and the
entry point that a sweep of it calls."""
from __future__ import annotations


def control(cfg: dict) -> dict:
    """The configuration with its stated guarantee broken (``control``):
    what the control runs in the program's place."""
    scale = cfg["control"]["spot_rate_scale"]
    return {**cfg, "regions": [{**r, "spot_rate": r["spot_rate"] * scale}
                               for r in cfg["regions"]]}


class Program:
    """Built once in set-up; each call is one ``run_region_sweep`` of the
    grid."""

    def __init__(self, cfg: dict):
        from repro.core import (Exponential, NoticeAwareKernel, Region,
                                RegionTopology, RoutingKernel)
        if cfg["kernel"] != "NoticeAwareKernel":
            raise ValueError(f"unknown kernel {cfg['kernel']!r}")
        self.cfg = cfg
        self.topology = RegionTopology(regions=tuple(
            Region(job=Exponential(r["job_rate"]),
                   spot=Exponential(r["spot_rate"]), price=r["price"],
                   hazard=r["hazard"], notice=r["notice"], rmax=r["rmax"])
            for r in cfg["regions"]))
        self.kernel = RoutingKernel(
            NoticeAwareKernel(checkpoint_time=cfg["checkpoint_hours"]),
            choice=cfg["routing"])

    def sweep(self, rs, key, *, n_seeds: int, n_events: int,
              burn_in: int) -> dict:
        from repro.core import run_region_sweep
        return run_region_sweep(self.topology, self.kernel, {"r": rs},
                                k=self.cfg["k"], n_events=n_events, key=key,
                                n_seeds=n_seeds, burn_in=burn_in,
                                **self.cfg["executor"])
