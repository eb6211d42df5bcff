"""The benchmark harness on the CPU: discovery by name, the refusal of a
host without a TPU, the copied closed forms, and every cell end to end at
a tiny size."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from chipbench_testlib import ROOT, run_tiny, tiny_root

from chipbench import bench

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_every_part_is_found_by_name():
    for w in BENCH["workloads"]:
        cell = bench.Cell.load(BENCH, w["name"])
        assert hasattr(cell.adapter, "Program")
        assert hasattr(cell.reference, "compare")
        assert set(cell.config["limits"]) >= {"malformed", "repeats"}
        assert getattr(cell.adapter.Program, cell.traffic["entry"])
    for c in BENCH["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        reader = bench.load_module(ROOT / "chipbench" / "metrics"
                                   / f"{m['name']}.py")
        assert callable(reader.read)
    for w in CELLS:  # each cell reports setup_s, another end-to-end
        e2e = {m["name"] for m in bench.cell_metrics(BENCH, w, "end_to_end")}
        layer = bench.cell_metrics(BENCH, w, "per_layer")
        assert "setup_s" in e2e and len(e2e) >= 2 and layer
        assert all(m["moves"] in e2e for m in layer)


def test_an_unknown_cell_is_refused():
    with pytest.raises(bench.Refused, match="no workload named"):
        bench.Cell.load(BENCH, "no.such.cell")


def test_the_measurement_path_refuses_a_host_without_a_tpu():
    with pytest.raises(bench.Refused, match="no TPU"):
        bench.tpu_devices(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 2, proc.stderr
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("k,lam,mu,n", [(10.0, 1 / 12, 1 / 24, 1),
                                        (10.0, 1 / 12, 1 / 24, 8),
                                        (4.0, 0.5, 0.5, 3),
                                        (7.5, 0.2, 0.9, 5)])
def test_copied_closed_forms_equal_the_programs(k, lam, mu, n):
    from repro.core import analytic, cost
    ref = bench.load_module(ROOT / "chipbench" / "configs"
                            / "fig2_single_queue_reference.py")
    assert ref.theorem5_cost(k, lam, mu, n) == analytic.theorem5_cost(
        k, lam, mu, n)
    for pi0 in (0.0, 0.3, 0.97):
        assert ref.theorem1_cost(k, lam, mu, pi0) == cost.theorem1_cost(
            k, lam, mu, pi0)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("workload", CELLS)
def test_each_cell_runs_and_checks_correct(tiny, workload):
    line = run_tiny(tiny, workload)
    assert line["correct"], line["checks"]
    assert line["attempted"] == 2 and line["failed"] == 0
    want = {m["name"] for m in bench.cell_metrics(BENCH, workload,
                                                  "end_to_end")}
    assert set(line["metrics"]) == want
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("calls", [1, 3])
def test_a_window_asked_for_calls_makes_that_many(tiny, calls):
    line = run_tiny(tiny, "market4.whatif", calls=calls)
    assert line["attempted"] == calls
    assert line["correct"], line["checks"]


def test_a_cell_is_added_with_new_files_and_entries_only(tiny):
    """A new mix and a new cell: data files and entries, no code."""
    mix = json.loads((tiny / "chipbench" / "traffic"
                      / "sweep_4k_2p18.json").read_text())
    mix.update(r={"linspace": [1.0, 3.0, 3]}, n_seeds=6, n_events=1500)
    (tiny / "chipbench" / "traffic" / "new_mix.json").write_text(
        json.dumps(mix))
    b = json.loads((tiny / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "fig2.new", "config": "fig2_single_queue",
                           "traffic": "new_mix", "chips": 1, "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "events_per_s":
            m["workloads"].append("fig2.new")
    (tiny / "BENCHMARK.json").write_text(json.dumps(b))
    line = run_tiny(tiny, "fig2.new")
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"events_per_s", "setup_s"}
    assert line["metrics"]["events_per_s"]["value"] > 0
    assert np.isfinite(line["checks"]["thm5_z"]["value"])
