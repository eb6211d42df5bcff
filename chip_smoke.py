#!/usr/bin/env python3
"""Smoke run of the sweep engine on TPU: does the main path run on the chip?

    python chip_smoke.py              # one chip: all three loops, both executors
    python chip_smoke.py --chips 4    # four chips: shard="lanes" vs one chip

One process drives the chip(s) through the user entry points
(``run_sweep`` / ``run_market_sweep`` / ``run_region_sweep``) at the
entry points' defaults (``tile=256``, ``rmax=64``, ``chunk_events=65536``)
and 4,096 lanes (64 r-values x 64 seeds), with the Pallas kernel compiled
(``impl="pallas", interpret=False, rng="slab"``) and the XLA executor
(``impl="xla"``).  It checks each result against a plain reference:

* single queue at the paper's Fig. 2 point (lambda=1/12, mu=1/24, k=10):
  at integer r the mean ``avg_cost`` matches the M/M/1/N closed form
  (``theorem5_cost``), and at every r the Theorem-1 cost law holds for
  the measured ``pi0_spot``;
* market and regions: exact per-lane accounting identities (the regions
  are the chip benchmark's ``region2_least_loaded``, revocations on);
* ``pallas`` vs ``xla``: the contract in :data:`XLA_CONTRACT`.

Tolerances are in units of the seed spread: |mean difference| <= Z_MAX
standard errors over the 64 seeds.  Per-phase lines (compile seconds,
steady seconds, events/s, worst deviation of each check) are a smoke
timing, not a benchmark.  The last stdout line is one JSON object
``{"ok": true, "device": {...}}``; any failed phase or check raises and
exits non-zero before it, as does a host whose JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

LAM, MU, K = 1 / 12, 1 / 24, 10.0
RMAX = 64
N_SEEDS = 64
BURN_IN = 1 << 14
SINGLE_EVENTS = 1 << 20
LOOP_EVENTS = 1 << 18  # market and region phases
SHARD_LANES = 16_384
Z_MAX = 5.0  # seed-spread standard errors a mean may sit from its reference

#: What ``pallas`` owes ``xla`` on the chip: integer event counts bitwise.
#: Float sums are held to the seed-spread tolerance like any other check.
XLA_CONTRACT = "ints bitwise, floats within Z_MAX seed-spread standard errors"

#: The compiled Pallas executor, named explicitly (never ``interpret=None``).
PALLAS = {"impl": "pallas", "interpret": False}

#: Mosaic kernels are looked for in the StableHLO that JAX lowers.
IR_DIR = ROOT / ".chip_smoke_ir"


def r_grid():
    """64 r-values over [0.25, 8.0] holding the integers 1..8."""
    import numpy as np
    return np.union1d(np.linspace(0.25, 8.0, 57),
                      np.arange(1.0, 9.0)).astype(np.float32)


class CheckFailed(AssertionError):
    pass


def check(name: str, worst: float, limit: float, unit: str = "") -> None:
    print(f"  check {name}: worst {worst!r}{unit} (limit {limit!r}{unit})")
    if not worst <= limit:
        raise CheckFailed(f"{name}: worst {worst!r} > limit {limit!r}")


def seed_z(a, b=None):
    """Worst |mean(a - b)| over the seed axis (last), in standard errors
    of that difference's seed spread."""
    import numpy as np
    d = np.asarray(a, np.float64) - (0.0 if b is None
                                     else np.asarray(b, np.float64))
    se = d.std(axis=-1, ddof=1) / np.sqrt(d.shape[-1])
    mean = d.mean(axis=-1)
    z = np.where(se > 0, np.abs(mean) / np.maximum(se, 1e-300),
                 np.where(mean == 0, 0.0, np.inf))
    return float(np.max(z))


def run_phase(name, call, lane_events):
    """Compile + one run, then one steady run; prints the smoke timing."""
    from repro.obs.timing import time_compiled
    out, t = time_compiled(call)
    print(f"phase {name}: compile_s={t['t_compile_s']!r} "
          f"steady_s={t['t_run_s']!r} "
          f"events_per_s={lane_events / t['t_run_s']!r} "
          f"(smoke timing, not a benchmark)")
    return out


def check_xla_contract(name, pal, xla, seed_stat):
    import numpy as np
    from repro.core.engine import INT_STATS
    bad = [n for n in INT_STATS if n in xla
           and not np.array_equal(np.asarray(xla[n]), np.asarray(pal[n]))]
    n_int = sum(n in xla for n in INT_STATS)
    print(f"  check {name} pallas==xla integer stats: "
          f"{n_int - len(bad)}/{n_int} bitwise")
    if bad:
        diff = {n: int(np.sum(np.asarray(xla[n]) != np.asarray(pal[n])))
                for n in bad}
        raise CheckFailed(f"{name}: integer stats differ from xla: {diff}")
    check(f"{name} pallas-xla {seed_stat}",
          seed_z(pal[seed_stat], xla[seed_stat]), Z_MAX, " se")
    same = all(np.array_equal(np.asarray(v), np.asarray(pal[n]))
               for n, v in xla.items())
    print(f"  {name} pallas==xla every stat bitwise: {same}")


def ir_dumps() -> set:
    """The lowered modules dumped so far."""
    return set(IR_DIR.glob("*.mlir"))


def mosaic_compiled(before: set) -> bool:
    """Whether a program lowered since the dumps ``before`` holds a Mosaic
    kernel: one jitted executor serves every loop (and the market's runs
    inside its jitted front), so a phase looks at what it lowered."""
    return any("tpu_custom_call" in p.read_text()
               for p in ir_dumps() - before)


def single_queue_phases(key):
    import numpy as np
    from repro.core import Exponential, ThreePhaseKernel, run_sweep
    from repro.core.analytic import theorem5_cost
    from repro.core.cost import theorem1_cost

    rs = r_grid()
    lane_events = rs.size * N_SEEDS * (SINGLE_EVENTS + BURN_IN)
    kw = dict(k=K, n_events=SINGLE_EVENTS, key=key, n_seeds=N_SEEDS,
              rmax=RMAX, burn_in=BURN_IN, rng="slab")
    job, spot, kern = Exponential(LAM), Exponential(MU), ThreePhaseKernel()
    before, outs = ir_dumps(), {}
    for impl, extra in (("xla", {"impl": "xla"}), ("pallas", PALLAS)):
        outs[impl] = run_phase(
            f"single_queue[{impl}] {rs.size * N_SEEDS} lanes x "
            f"{SINGLE_EVENTS} events",
            lambda: run_sweep(job, spot, kern, {"r": rs}, **extra, **kw),
            lane_events)
    if not mosaic_compiled(before):
        raise CheckFailed("single_queue: no tpu_custom_call in the pallas "
                          "executor's lowering")
    for impl, out in outs.items():
        cost = np.asarray(out["avg_cost"])
        if cost.shape != (rs.size, N_SEEDS) or not np.all(np.isfinite(cost)):
            raise CheckFailed(f"single_queue[{impl}]: avg_cost of shape "
                              f"{cost.shape} or non-finite")
        closed = out["spot_served"] + out["ondemand"]
        check(f"single_queue[{impl}] jobs_completed - (spot + on-demand)",
              float(np.max(np.abs(out["jobs_completed"] - closed))), 0.0)
        ints = np.flatnonzero(rs == np.round(rs))
        theory5 = np.array([theorem5_cost(K, LAM, MU, int(rs[i]))
                            for i in ints])
        check(f"single_queue[{impl}] avg_cost vs theorem5_cost (r=1..8)",
              seed_z(cost[ints], theory5[:, None]), Z_MAX, " se")
        law = np.vectorize(lambda p: theorem1_cost(K, LAM, MU, p))(
            np.asarray(out["pi0_spot"], np.float64))
        check(f"single_queue[{impl}] Theorem-1 law at every r",
              seed_z(cost, law), Z_MAX, " se")
    check_xla_contract("single_queue", outs["pallas"], outs["xla"],
                       "avg_cost")


def market_phases(key):
    import numpy as np
    from repro.core import (Exponential, NoticeAwareKernel, SpotMarket,
                            SpotPool, run_market_sweep)

    market = SpotMarket(pools=(
        SpotPool(Exponential(MU / 4), price=0.5, hazard=0.02, notice=0.5),
        SpotPool(Exponential(MU / 4), price=0.3, hazard=0.05, notice=0.01),
        SpotPool(Exponential(MU / 4), price=0.2, hazard=0.0),
        SpotPool(Exponential(MU / 4), price=0.1, hazard=0.10, notice=2.0),
    ))
    kern = NoticeAwareKernel(checkpoint_time=0.05)
    rs = r_grid()
    lane_events = rs.size * N_SEEDS * (LOOP_EVENTS + BURN_IN)
    kw = dict(k=K, n_events=LOOP_EVENTS, key=key, n_seeds=N_SEEDS,
              rmax=RMAX, burn_in=BURN_IN, rng="slab")
    before, outs = ir_dumps(), {}
    for impl, extra in (("xla", {"impl": "xla"}), ("pallas", PALLAS)):
        outs[impl] = run_phase(
            f"market[{impl}] 4 pools, {rs.size * N_SEEDS} lanes x "
            f"{LOOP_EVENTS} events",
            lambda: run_market_sweep(Exponential(LAM), market, kern,
                                     {"r": rs}, **extra, **kw),
            lane_events)
    if not mosaic_compiled(before):
        raise CheckFailed("market: no tpu_custom_call in the pallas "
                          "executor's lowering")
    for impl, out in outs.items():
        served = np.asarray(out["pool_served"]).sum(axis=-1)
        check(f"market[{impl}] sum(pool_served) - spot_served",
              float(np.max(np.abs(served - out["spot_served"]))), 0.0)
        # a leg closes by spot service, on-demand, or a preempted resume
        legs = out["spot_served"] + out["ondemand"] + out["resumed"]
        check(f"market[{impl}] jobs_completed - closed legs",
              float(np.max(np.abs(out["jobs_completed"] - legs))), 0.0)
        if not np.all(np.isfinite(np.asarray(out["avg_cost_job"]))):
            raise CheckFailed(f"market[{impl}]: non-finite avg_cost_job")
    check_xla_contract("market", outs["pallas"], outs["xla"],
                       "avg_cost_job")


def region_phases(key):
    """The chip benchmark's two spot regions (``region2_least_loaded``),
    hazards and notice on: routing, per-region admission and the
    revocation and resume branch."""
    import numpy as np
    from repro.core import (Exponential, NoticeAwareKernel, Region,
                            RegionTopology, RoutingKernel, run_region_sweep)

    cfg = json.loads((ROOT / "chipbench" / "configs"
                      / "region2_least_loaded.json").read_text())
    topo = RegionTopology(regions=tuple(
        Region(job=Exponential(g["job_rate"]),
               spot=Exponential(g["spot_rate"]), price=g["price"],
               hazard=g["hazard"], notice=g["notice"], rmax=g["rmax"])
        for g in cfg["regions"]))
    kern = RoutingKernel(
        NoticeAwareKernel(checkpoint_time=cfg["checkpoint_hours"]),
        choice=cfg["routing"])
    rs = r_grid()
    lane_events = rs.size * N_SEEDS * (LOOP_EVENTS + BURN_IN)
    kw = dict(k=cfg["k"], n_events=LOOP_EVENTS, key=key, n_seeds=N_SEEDS,
              burn_in=BURN_IN, rng="slab")  # rmax: Region.rmax, 64
    before, outs = ir_dumps(), {}
    for impl, extra in (("xla", {"impl": "xla"}), ("pallas", PALLAS)):
        outs[impl] = run_phase(
            f"regions[{impl}] 2 regions, {rs.size * N_SEEDS} lanes x "
            f"{LOOP_EVENTS} events",
            lambda: run_region_sweep(topo, kern, {"r": rs}, **extra,
                                     **kw), lane_events)
    if not mosaic_compiled(before):
        raise CheckFailed("regions: no tpu_custom_call in the pallas "
                          "executor's lowering")
    for impl, out in outs.items():
        served = np.asarray(out["region_served"]).sum(axis=-1)
        check(f"regions[{impl}] sum(region_served) - spot_served",
              float(np.max(np.abs(served - out["spot_served"]))), 0.0)
        # a leg closes by spot service, on-demand, or a preempted resume
        legs = out["spot_served"] + out["ondemand"] + out["resumed"]
        check(f"regions[{impl}] jobs_completed - closed legs",
              float(np.max(np.abs(out["jobs_completed"] - legs))), 0.0)
        routed = np.asarray(out["region_jobs"]).sum(axis=-1)
        check(f"regions[{impl}] sum(region_jobs) - jobs_arrived",
              float(np.max(np.abs(routed - out["jobs_arrived"]))), 0.0)
        if not (np.sum(out["resumed"]) > 0
                and np.all(np.sum(out["region_preempted"], axis=(0, 1)) > 0)):
            raise CheckFailed(f"regions[{impl}]: no revocation or no resume "
                              f"in some region")
        if not np.all(np.isfinite(np.asarray(out["avg_cost_job"]))):
            raise CheckFailed(f"regions[{impl}]: non-finite avg_cost_job")
    check_xla_contract("regions", outs["pallas"], outs["xla"],
                       "avg_cost_job")


def sharded_phases(key, n_chips: int):
    """``shard="lanes"`` over ``n_chips`` vs the same lanes on one chip."""
    import numpy as np
    from repro.core import Exponential, ThreePhaseKernel, run_sweep
    from repro.core.engine import INT_STATS
    from repro.distributed.sharding import lane_mesh

    rs = np.linspace(0.25, 8.0, SHARD_LANES // N_SEEDS, dtype=np.float32)
    lane_events = SHARD_LANES * (LOOP_EVENTS + BURN_IN)
    kw = dict(k=K, n_events=LOOP_EVENTS, key=key, n_seeds=N_SEEDS,
              rmax=RMAX, burn_in=BURN_IN, rng="slab", **PALLAS)
    job, spot, kern = Exponential(LAM), Exponential(MU), ThreePhaseKernel()
    before = ir_dumps()
    sharded = run_phase(
        f"single_queue[pallas] shard=lanes over {n_chips} chips, "
        f"{SHARD_LANES} lanes x {LOOP_EVENTS} events",
        lambda: run_sweep(job, spot, kern, {"r": rs}, shard="lanes",
                          mesh=lane_mesh(n_chips), **kw), lane_events)
    if not mosaic_compiled(before):
        raise CheckFailed("sharded: no tpu_custom_call in the sharded "
                          "executor's lowering")
    one = run_phase(
        f"single_queue[pallas] unsharded on 1 chip, {SHARD_LANES} lanes "
        f"x {LOOP_EVENTS} events",
        lambda: run_sweep(job, spot, kern, {"r": rs}, **kw), lane_events)
    bad = [n for n in INT_STATS if n in one
           and not np.array_equal(np.asarray(one[n]), np.asarray(sharded[n]))]
    n_int = sum(n in one for n in INT_STATS)
    print(f"  check sharded==unsharded integer stats: "
          f"{n_int - len(bad)}/{n_int} bitwise")
    if bad:
        raise CheckFailed(f"sharded integer stats differ: {bad}")
    floats_equal = all(np.array_equal(np.asarray(v), np.asarray(sharded[n]))
                       for n, v in one.items())
    print(f"  sharded==unsharded every stat bitwise: {floats_equal}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the shard='lanes' path over four "
                         "chips and its one-chip comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1

    from repro.obs.timing import enable_compile_cache, provenance
    cache = enable_compile_cache()
    warm = sum(1 for _ in pathlib.Path(cache).glob("*"))
    shutil.rmtree(IR_DIR, ignore_errors=True)
    jax.config.update("jax_dump_ir_to", str(IR_DIR))
    jax.config.update("jax_include_debug_info_in_dumps", False)
    stamp = provenance(seed=args.seed, compile_cache=cache,
                       compile_cache_entries_at_start=warm)
    print("provenance " + json.dumps(stamp))

    key = jax.random.key(args.seed)
    t0 = time.perf_counter()
    if args.chips == 1:
        single_queue_phases(key)
        market_phases(key)
        region_phases(key)
        print(f"pallas vs xla contract held: {XLA_CONTRACT}")
    else:
        sharded_phases(key, args.chips)
    print(f"all phases passed in {time.perf_counter() - t0!r} s")
    shutil.rmtree(IR_DIR, ignore_errors=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
