"""Helpers of the harness tests: a copy of the benchmark shrunk to a size
that the CPU runs in seconds, with the Pallas kernel interpreted."""
from __future__ import annotations

import json
import math
import pathlib
import shutil
import time
from unittest import mock

ROOT = pathlib.Path(__file__).resolve().parents[2]


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """``BENCHMARK.json`` and ``chipbench/`` copied under ``tmp``, every
    mix cut to 3 r-values (two of them integers) x 8 seeds x 2,000
    events, and every executor left to pick the interpreter."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    for path in (tmp / "chipbench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        cfg["executor"]["interpret"] = None
        path.write_text(json.dumps(cfg))
    for path in (tmp / "chipbench" / "traffic").glob("*.json"):
        mix = json.loads(path.read_text())
        mix.update(r={"linspace": [0.5, 2.0, 3]}, n_seeds=8, n_events=2000,
                   burn_in=200 if mix["burn_in"] else 0)
        path.write_text(json.dumps(mix))
    return tmp


def run_tiny(root: pathlib.Path, workload: str, seed: int = 11, *,
             calls: int = 2, control: bool = False) -> dict:
    """One untraced run of ``workload`` on the CPU, its window ``calls``
    calls however long they take: no look for a chip, and no persistent
    compilation cache written."""
    import jax
    from chipbench import bench
    with mock.patch.object(bench, "enable_compile_cache"):
        return bench.run_cell(workload, seed, math.inf, False,
                              t_start=time.perf_counter(), root=root,
                              devices=jax.devices(), control=control,
                              calls=calls)
