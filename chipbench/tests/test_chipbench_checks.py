"""The comparison that decides ``correct`` can fail: the configuration's
control, each fault that a cell's timed path can have, and a fault in
each law the market configurations state (admission, the notice law),
read as not correct through the whole of a run (at a tiny size, on the
CPU)."""
from __future__ import annotations

import json

import numpy as np
import pytest

from chipbench_testlib import ROOT, run_tiny, tiny_root

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
    """Wrap every entry point the configurations call."""
    import repro.core
    from repro.cluster.orchestrator import SpotCluster
    first = []

    def wrap(fn):
        def broken(*args, **kwargs):
            answer = fn(*args, **kwargs)
            if not first:
                first.append(answer)
            return fault(answer, first[0])
        return broken

    for name in ("run_sweep", "run_market_sweep"):
        monkeypatch.setattr(repro.core, name, wrap(getattr(repro.core, name)))
    monkeypatch.setattr(SpotCluster, "what_if_sweep",
                        wrap(SpotCluster.what_if_sweep))


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_timed_path_reads_not_correct(tiny, monkeypatch,
                                                     workload, fault):
    _break(monkeypatch, FAULTS[fault])
    line = run_tiny(tiny, workload, seconds=0.6)
    assert line["attempted"] >= 2
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
    line = run_tiny(tiny, workload, seconds=0.6)
    assert not line["correct"], line["checks"]


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_reads_not_correct(tiny, workload):
    sound = run_tiny(tiny, workload, seed=5, seconds=0.6)
    control = run_tiny(tiny, workload, seed=5, seconds=0.6, control=True)
    assert sound["correct"], sound["checks"]
    assert not control["correct"], control["checks"]
    over = [n for n, c in control["checks"].items() if c["value"] > c["limit"]]
    assert over and all(n.endswith("_z") for n in over), over
