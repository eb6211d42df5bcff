#!/usr/bin/env python3
"""Record a cell's reduced trace for the harness tests.

    python3 chipbench/tools/record_trace.py fig2.deep OUT.json [--seed N]

Makes one traced run of the cell (as ``run.py --trace 1`` does), prints
its result line, and writes the reduced trace to ``OUT.json``: the device
ops of the traced window and the host events on the benchmark's threads
that last 1 ms or more, or are the benchmark's own spans.  Needs a TPU.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))

#: Host events shorter than this are left out of the recording.
MIN_HOST_S = 1e-3


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("workload")
    ap.add_argument("out", type=pathlib.Path)
    ap.add_argument("--seed", type=int, default=2**31 + 7)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    from chipbench import bench, trace
    from chipbench.generator import r_grid
    full = args.out.with_suffix(".full.json")
    result = bench.run_cell(args.workload, args.seed, args.seconds, True,
                            t_start=T_START, keep_trace=full)
    print(json.dumps(result))
    tr = json.loads(full.read_text())
    full.unlink()
    lo, hi = trace.Trace(tr["ops"], tr["spans"]).window()
    ops = {d: [op for op in evs if op[1] > lo and op[0] < hi]
           for d, evs in tr["ops"].items()}
    spans = [s for s in tr["spans"]
             if s[1] > lo - 1.0 and s[0] < hi
             and (s[2].startswith(trace.SPAN_PREFIX)
                  or s[1] - s[0] >= MIN_HOST_S)]
    mix = bench.Cell.load(bench.load_benchmark(), args.workload).traffic
    per_call = (r_grid(mix["r"]).size * int(mix["n_seeds"])
                * (int(mix["n_events"]) + int(mix["burn_in"])))
    args.out.write_text(json.dumps({"workload": args.workload,
                                    "lane_events_per_call": per_call,
                                    "device": result["device"],
                                    "metrics": result["metrics"],
                                    "ops": ops, "spans": spans}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
