#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 chipbench/run.py --workload fig2.deep --seed 7 --seconds 10 --trace 0

From the root of a checkout that holds ``BENCHMARK.json``.  Set-up builds
the cell from the seed and makes one call at the window's exact shapes;
the window then issues whole calls back to back until the first that ends
after ``--seconds``.  ``--trace 1`` profiles the window (a few calls) in a
run of its own and reports the per-layer metrics.  The last stdout line is
one JSON object; the compared numbers and their limits are the last lines
of stderr.  A host whose JAX finds no TPU, or fewer chips than the cell
asks for, exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# The repo root (for the ``chipbench`` package) in place of this script's
# directory, whose module names would shadow the standard library's.
sys.path[0] = str(ROOT)
sys.path.insert(1, str(ROOT / "src"))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from chipbench import bench
    try:
        result = bench.run_cell(args.workload, args.seed, args.seconds,
                                bool(args.trace), t_start=T_START)
    except bench.Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
