"""The comparison that decides ``correct`` can fail: the configuration's
control, each fault that a cell's timed path can have (in every cell,
and in a cell added on an entry point that no cell calls), and a fault
in each law the market configurations state (admission, the notice
law), read as not correct through the whole of a run (at a tiny size,
on the CPU)."""
from __future__ import annotations

import json

import numpy as np
import pytest

from chipbench_testlib import ROOT, run_tiny, tiny_root

from chipbench import bench, stats

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
MARKET_CELLS = [w["name"] for w in BENCH["workloads"]
                if w["config"].startswith("market4_")]


def _stale(answer, first):
    """A step that returns its state unchanged: every call answers as the
    first did."""
    return first


def _half_batch(answer, first):
    """Half of the batch left out: the second half of the seeds is the
    first half again."""
    out = {}
    for name, x in answer.items():
        x = np.array(x)
        h = x.shape[1] // 2
        x[:, h:2 * h] = x[:, :h]
        out[name] = x
    return out


def _altered(answer, first):
    """An answer altered where it is produced: one lane's count."""
    out = {k: np.array(v) for k, v in answer.items()}
    out["jobs_completed"][0, 0] += 1
    return out


#: Each fault that the cells can have; none runs over several chips, so
#: none can leave out an exchange between them.
FAULTS = {"stale": _stale, "half_batch": _half_batch, "altered": _altered}


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def _break(monkeypatch, fault):
    """Wrap the entry that each cell's traffic names on the program that
    set-up builds (``Program.<entry>``, as ``generator.Load`` binds it),
    whatever the entry calls underneath."""
    prepare = bench.prepare
    first = []

    def broken_prepare(cell, control=False):
        program = prepare(cell, control)
        entry = cell.traffic["entry"]
        fn = getattr(program, entry)

        def broken(*args, **kwargs):
            answer = fn(*args, **kwargs)
            if not first:
                first.append(answer)
            return fault(answer, first[0])
        setattr(program, entry, broken)
        return program

    monkeypatch.setattr(bench, "prepare", broken_prepare)


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_timed_path_reads_not_correct(tiny, monkeypatch,
                                                     workload, fault):
    _break(monkeypatch, FAULTS[fault])
    line = run_tiny(tiny, workload)
    assert line["attempted"] == 2
    assert not line["correct"], line["checks"]


@pytest.fixture
def fresh_programs():
    """Programs traced before and after a law is broken are not reused."""
    import jax
    jax.clear_caches()
    yield
    jax.clear_caches()


def _admit_one_more(monkeypatch):
    """Admission broken: every kernel admits as if r were one higher."""
    from repro.core import market, policies
    law = policies.three_phase_admit_prob
    for module in (policies, market):
        monkeypatch.setattr(module, "three_phase_admit_prob",
                            lambda qlen, r: law(qlen, r + 1))


def _never_fits(monkeypatch):
    """The notice law broken: no checkpoint fits any notice."""
    from repro.core import market
    monkeypatch.setattr(market, "checkpoint_within_notice",
                        lambda checkpoint_time, notice: notice < 0)


@pytest.mark.parametrize("workload,law", [(w, "admission") for w in CELLS]
                         + [(w, "notice") for w in MARKET_CELLS])
def test_a_broken_law_reads_not_correct(tiny, monkeypatch, fresh_programs,
                                        workload, law):
    {"admission": _admit_one_more, "notice": _never_fits}[law](monkeypatch)
    line = run_tiny(tiny, workload)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_reads_not_correct(tiny, workload):
    sound = run_tiny(tiny, workload, seed=5)
    control = run_tiny(tiny, workload, seed=5, control=True)
    assert sound["correct"], sound["checks"]
    assert not control["correct"], control["checks"]
    over = [n for n, c in control["checks"].items() if c["value"] > c["limit"]]
    assert over and all(n.endswith("_z") for n in over), over


# ------------------------------------- a cell on an entry no cell calls
REGION_CELL = "region2.deep"
REGION_CONFIG = {
    "name": "region2_least_loaded",
    "k": 10.0,
    "regions": [
        {"job_rate": 1 / 24, "spot_rate": 1 / 48, "price": 1.0,
         "hazard": 0.0002635231406129536, "notice": 1 / 30, "rmax": 64},
        {"job_rate": 1 / 24, "spot_rate": 1 / 48, "price": 2.0,
         "hazard": 3.46819287456026e-05, "notice": 1 / 30, "rmax": 64}],
    "checkpoint_hours": 0.025,
    "routing": "least_loaded",
    "executor": {"impl": "xla", "rng": "slab"},
    "limits": {"malformed": 0, "repeats": 0, "ledger_gap": 0,
               "horizon_z": 6.0},
}
REGION_ADAPTER = '''"""Two regions on the program: ``run_region_sweep`` with the
configuration's routing over the notice-aware policy."""


class Program:
    def __init__(self, cfg):
        from repro.core import (Exponential, NoticeAwareKernel, Region,
                                RegionTopology, RoutingKernel)
        self.cfg = cfg
        self.topology = RegionTopology(regions=tuple(
            Region(job=Exponential(r["job_rate"]),
                   spot=Exponential(r["spot_rate"]), price=r["price"],
                   hazard=r["hazard"], notice=r["notice"], rmax=r["rmax"])
            for r in cfg["regions"]))
        self.kernel = RoutingKernel(
            NoticeAwareKernel(checkpoint_time=cfg["checkpoint_hours"]),
            choice=cfg["routing"])

    def sweep(self, rs, key, *, n_seeds, n_events, burn_in):
        from repro.core import run_region_sweep
        return run_region_sweep(self.topology, self.kernel, {"r": rs},
                                k=self.cfg["k"], n_events=n_events, key=key,
                                n_seeds=n_seeds, burn_in=burn_in,
                                **self.cfg["executor"])
'''
REGION_REFERENCE = '''"""Generic checks of a region deployment's answers: well formed, no
repeats, the leg ledger over the regions, the simulated hours."""
import numpy as np

from chipbench import stats

STATS = ("jobs_arrived", "jobs_completed", "spot_served", "ondemand",
         "resumed", "time", "region_served", "region_jobs")


def compare(cfg, traffic, rs, answers):
    shape = (rs.size, traffic["n_seeds"])
    good = [a for a in answers if stats.well_formed(a, STATS, shape)]
    out = {"malformed": len(answers) - len(good)}
    if not good:
        return {**out, **{n: float("inf") for n in cfg["limits"]
                          if n != "malformed"}}
    out["repeats"] = stats.repeats(good)
    get = lambda name: stats.stack(good, name)
    spot, ondemand = get("spot_served"), get("ondemand")
    rmax = sum(r["rmax"] for r in cfg["regions"])
    gaps = [np.abs(get("jobs_completed") - (spot + ondemand + get("resumed"))),
            np.abs(get("region_served").sum(axis=-1) - spot),
            np.abs(get("region_jobs").sum(axis=-1) - get("jobs_arrived")),
            np.abs(get("jobs_arrived") - (spot + ondemand)) - rmax]
    out["ledger_gap"] = float(max(max(np.max(g) for g in gaps), 0.0))
    rate = sum(r["job_rate"] + r["spot_rate"] + r["hazard"]
               for r in cfg["regions"])
    out["horizon_z"] = stats.horizon_z(get("time"), traffic["n_events"], rate)
    return out
'''


@pytest.fixture(scope="module")
def region_root(tmp_path_factory):
    """A tiny copy with a two-region cell added as new files and new
    entries only: its configuration, adapter and reference."""
    root = tiny_root(tmp_path_factory.mktemp("region"))
    configs = root / "chipbench" / "configs"
    name = REGION_CONFIG["name"]
    (configs / f"{name}.json").write_text(json.dumps(REGION_CONFIG))
    (configs / f"{name}.py").write_text(REGION_ADAPTER)
    (configs / f"{name}_reference.py").write_text(REGION_REFERENCE)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["configs"].append({"name": name, "source": "test", "file":
                         f"chipbench/configs/{name}.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": REGION_CELL, "config": name,
                           "traffic": "sweep_4k_2p17", "chips": 1,
                           "why": "test"})
    for m in b["end_to_end"]:
        if m["name"] == "events_per_s":
            m["workloads"].append(REGION_CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


@pytest.mark.parametrize("fault", ["sound"] + sorted(FAULTS))
def test_a_cell_on_another_entry_point_is_faulted_through_its_entry(
        region_root, monkeypatch, fault):
    """``run_region_sweep``, which no benchmark cell calls, is faulted
    with no change to the tests or the harness."""
    if fault != "sound":
        _break(monkeypatch, FAULTS[fault])
    line = run_tiny(region_root, REGION_CELL)
    assert line["attempted"] == 2
    assert line["correct"] == (fault == "sound"), line["checks"]


# ------------------------------------------------------------ repeats
def _answer(seed: int) -> dict:
    """Distinct lanes of a (3 grid points, 8 seeds) answer."""
    rng = np.random.default_rng(seed)
    return {name: rng.integers(0, 10**6, (3, 8)).astype(np.float64)
            for name in stats.LANE_ROW}


def test_seeds_with_equal_hours_and_other_counts_are_no_repeats():
    a = _answer(1)
    a["time"][:, 1] = a["time"][:, 0]  # equal at every grid point
    assert stats.repeats([a, _answer(2)]) == 0


@pytest.mark.parametrize("fault", ["half_batch", "stale"])
def test_copied_lanes_or_answers_are_repeats(fault):
    first, second = _answer(1), _answer(2)
    assert stats.repeats([first, FAULTS[fault](second, first)]) > 0
