#!/usr/bin/env python3
"""Readings that the limits are set from: the compared numbers of sound
windows over many seeds and of the control over a few, in one process.

    python3 chipbench/readings.py --workload fig2.deep --seeds 12 \\
        --control-seeds 3 --seconds 10

Each window is one at the cell's own size and load, as a run makes it;
the program is set up once.  One JSON line per window goes to stdout.
The benchmark's own runs never run the control.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

#: Seeds past 31 bits, as large as a run's may be.
FIRST_SEED = 2**31 + 1000


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first-seed", type=int, default=FIRST_SEED)
    args = ap.parse_args()

    from chipbench import bench
    from chipbench.generator import Load

    b = bench.load_benchmark()
    cell = bench.Cell.load(b, args.workload)
    try:
        bench.tpu_devices(int(cell.workload["chips"]))
    except bench.Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    bench.enable_compile_cache()
    programs = {c: bench.prepare(cell, c) for c in (False, True)}
    for program in programs.values():  # compiled or cache-loaded
        Load(program, cell.traffic, 0).call(0)
    print(f"set-up {time.perf_counter() - T_START!r} s", file=sys.stderr)

    seeds = [(args.first_seed + i, False) for i in range(args.seeds)]
    seeds += [(args.first_seed + i, True) for i in range(args.control_seeds)]
    for seed, control in seeds:
        load = Load(programs[control], cell.traffic, seed)
        run = bench.Run(setup_s=0.0,
                        lane_events_per_call=load.lane_events)
        bench.run_window(load, run, args.seconds)
        compared = bench.checks(cell, load.rs, run.answers)
        line = json.dumps({
            "workload": args.workload, "seed": seed, "control": control,
            "calls": len(run.calls), "window_s": run.window_s,
            "lane_events_per_s": run.lane_events / run.window_s,
            "passed": bench.passed(compared),
            "checks": {n: c["value"] for n, c in compared.items()}})
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
