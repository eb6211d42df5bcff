"""Benchmark harness: one function per paper table/figure, the sweep-engine
throughput bench, and the roofline table from dry-run artifacts.

    PYTHONPATH=src:. python benchmarks/run.py [--smoke] [--json PATH]
        [--only SUBSTR] [--interpret]

Prints ``name,us_per_call,derived`` CSV.  ``--smoke`` shrinks event counts
(~20× fewer events) so the whole suite runs in a couple of minutes on CPU —
statistical targets in the derived strings only hold at full scale, but the
sweep-engine speedup numbers still land in BENCH_sweep.json.  ``--json``
additionally dumps all rows (plus per-bench headline scalars) to PATH.
The Pallas kernel bench times the compiled kernel and needs a TPU;
``--interpret`` times its interpreter instead.  JAX's persistent compile
cache is on (:func:`repro.obs.timing.enable_compile_cache`).
"""
from __future__ import annotations

import argparse
import json
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="scale event counts down ~20x")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also dump rows to a BENCH_*.json file")
    ap.add_argument("--only", default=None, metavar="SUBSTR",
                    help="run only benches whose name contains SUBSTR")
    ap.add_argument("--interpret", action="store_true",
                    help="time the Pallas kernel through its interpreter "
                         "(hosts without a TPU)")
    args = ap.parse_args()

    from repro.obs.timing import enable_compile_cache
    enable_compile_cache()

    from benchmarks import deadline_bench
    from benchmarks import engine_kernel_bench
    from benchmarks import env_bench
    from benchmarks import event_rng_bench
    from benchmarks import fleet_bench
    from benchmarks import market_bench
    from benchmarks import obs_bench
    from benchmarks import paper_benches as pb
    from benchmarks import region_bench
    from benchmarks import sweep_bench
    from benchmarks.roofline import bench_engine_roofline, bench_roofline

    engine_kernel_bench.set_interpret(args.interpret)
    if args.smoke:
        pb.set_scale(0.05)
        sweep_bench.set_scale(0.1)
        market_bench.set_scale(0.1)
        engine_kernel_bench.set_scale(0.1)
        region_bench.set_scale(0.1)
        event_rng_bench.set_scale(0.1)
        obs_bench.set_scale(0.1)
        env_bench.set_scale(0.1)
        deadline_bench.set_scale(0.1)
        fleet_bench.set_scale(0.1)

    benches = [
        pb.bench_theorem1_cost_law,
        pb.bench_fig2_bathtub_strong,
        pb.bench_fig3_bathtub_relaxed,
        pb.bench_fig4_mm_strong,
        pb.bench_fig5_mm_relaxed,
        pb.bench_theorem5_table,
        pb.bench_waittime_optimality,
        sweep_bench.bench_sweep_engine,  # writes BENCH_sweep.json
        market_bench.bench_market_engine,  # writes BENCH_market.json
        engine_kernel_bench.bench_engine_kernel,  # BENCH_engine_kernel.json
        region_bench.bench_region_engine,  # writes BENCH_region.json
        event_rng_bench.bench_event_rng,  # writes BENCH_event_rng.json
        obs_bench.bench_telemetry_overhead,  # writes BENCH_obs.json
        env_bench.bench_env_overhead,  # writes BENCH_env.json
        deadline_bench.bench_deadline,  # writes BENCH_deadline.json
        fleet_bench.bench_fleet_scaling,  # writes BENCH_fleet.json
        bench_engine_roofline,  # reads them back
        bench_roofline,
    ]
    if args.only:
        benches = [b for b in benches if args.only in b.__name__]
    print("name,us_per_call,derived")
    all_rows = []
    failures = 0
    for bench in benches:
        try:
            rows, headline = bench()
            for row in rows:
                derived = str(row["derived"]).replace(",", ";")
                print(f"{row['name']},{row['us_per_call']:.0f},{derived}")
            all_rows.append({"bench": bench.__name__, "rows": rows,
                             "headline": float(headline)})
        except Exception as exc:  # keep the harness going
            failures += 1
            print(f"{bench.__name__},0,ERROR: {exc}", file=sys.stdout)
            all_rows.append({"bench": bench.__name__, "error": str(exc)})
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"smoke": args.smoke, "benches": all_rows}, f,
                      indent=2, default=str)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
