"""The program's phase spans and compile markers, read from a profile:
the readers of ``chipbench/spans.py`` on synthetic traces, and a real
CPU profile of the entry points reduced by ``chipbench.trace.load``."""
from __future__ import annotations

import math

import pytest

from chipbench_testlib import ROOT

from chipbench import bench, spans
from chipbench.trace import Trace

METRICS = ROOT / "chipbench" / "metrics"
#: Each reader of a phase span, with the phases it sums.
PHASE_READERS = {
    "prep_ms.whatif": (spans.PREP,),
    "dispatch_ms.whatif": (spans.DISPATCH,),
    "fetch_ms.whatif": (spans.FETCH,),
    "summarize_ms.whatif": (spans.SUMMARIZE,),
    "host_phase_ms.events": spans.HOST_PHASES,
}
COMPILE_READERS = ("compiles.whatif", "compiles.events")
READERS = tuple(PHASE_READERS) + COMPILE_READERS


def _read(name, run):
    return bench.load_module(METRICS / f"{name}.py").read(run)


def _run(tr: Trace) -> bench.Run:
    calls = [tuple(c) for c in tr.calls()]
    return bench.Run(setup_s=1.0, lane_events_per_call=10, calls=calls,
                     answers=[{}] * len(calls), trace=tr)


def _phased_call(t0: float, entry: str = "repro.run_sweep[xla]") -> list:
    """One 10 s call at ``t0``: prep 1 s, dispatch 0.5 s, wait 7 s,
    fetch 0.25 s, summarize 1 s, inside the entry span [t0, t0 + 9.75]."""
    out, t = [[t0, t0 + 9.75, entry]], t0
    for name, d in zip(spans.PHASES, (1.0, 0.5, 7.0, 0.25, 1.0)):
        out.append([t, t + d, name])
        t += d
    return out


def _synthetic() -> Trace:
    """Two calls of 10 s after a warm-up call outside the window; a
    compile marker in the warm-up and one in the second call's prep."""
    s = [[0.0, 10.0, "bench.call"], [10.0, 20.0, "bench.call"],
         [-12.0, 0.0, "bench.warmup"]]
    s += _phased_call(-11.0) + _phased_call(0.0) + _phased_call(10.0)
    s += [[-10.5, -10.5, spans.COMPILED], [10.5, 10.5, spans.COMPILED]]
    return Trace({"/device:TPU:0": [[1.0, 2.0, "op", "fusion"]]}, s)


@pytest.mark.parametrize("name", READERS)
def test_readers_on_a_synthetic_trace(name):
    got = _read(name, _run(_synthetic()))
    expect = {"prep_ms.whatif": 1e3, "dispatch_ms.whatif": 500.0,
              "fetch_ms.whatif": 250.0, "summarize_ms.whatif": 1e3,
              "host_phase_ms.events": 2750.0,
              "compiles.whatif": 1, "compiles.events": 1}[name]
    assert got == pytest.approx(expect)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_phase_spans_reads_nothing(name):
    """The spans of a program that ends its entry span at dispatch, as
    before the phases: no reading, and no compile count of 0."""
    tr = Trace({"/device:TPU:0": [[1.0, 2.0, "op", "fusion"]]},
               [[0.0, 10.0, "bench.call"],
                [0.1, 0.2, "repro.run_sweep[xla]"],
                [0.1, 0.1, spans.COMPILED]])
    assert _read(name, _run(tr)) is None
    untraced = bench.Run(setup_s=1.0, lane_events_per_call=1)
    assert _read(name, untraced) is None


@pytest.mark.parametrize("name", COMPILE_READERS)
def test_no_compile_in_a_phased_window_reads_zero(name):
    s = [[0.0, 10.0, "bench.call"]] + _phased_call(0.0)
    assert _read(name, _run(Trace({}, s))) == 0


# ------------------------------------------------------- a real profile
LAM, MU = 1 / 12, 1 / 24


@pytest.fixture(scope="module")
def program():
    """Tiny entry-point calls, each compiled once before any profile."""
    import jax
    import jax.numpy as jnp

    from repro.cluster.orchestrator import (OnlineAdmissionController,
                                            SpotCluster)
    from repro.core import (Exponential, NoticeAwareKernel, SpotMarket,
                            SpotPool, ThreePhaseKernel, run_market_sweep,
                            run_sweep)

    market = SpotMarket(pools=(
        SpotPool(Exponential(MU / 2), price=1.0, hazard=0.02, notice=0.03),
        SpotPool(Exponential(MU / 2), price=2.0, hazard=0.01, notice=0.03)))
    cluster = SpotCluster(job_process=Exponential(LAM), market=market,
                          k_cost=10.0,
                          controller=OnlineAdmissionController(delta=12.0),
                          checkpoint_hours=0.025)
    key = jax.random.key(5)
    notice = NoticeAwareKernel(checkpoint_time=0.025)
    calls = {
        "repro.run_sweep[xla]": lambda n: run_sweep(
            Exponential(LAM), Exponential(MU), ThreePhaseKernel(),
            {"r": jnp.linspace(0.5, 2.0, n)}, n_events=400, key=key,
            n_seeds=2, rmax=8),
        "repro.run_market_sweep[xla]": lambda n: run_market_sweep(
            Exponential(LAM), market, notice,
            {"r": jnp.linspace(0.5, 2.0, n)}, n_events=400, key=key,
            n_seeds=2, rmax=8),
        "repro.cluster.what_if_sweep[market]":
            lambda n: cluster.what_if_sweep(
                [0.5 + 0.5 * i for i in range(n)], n_events=400, n_seeds=2,
                key=key),
    }
    for call in calls.values():
        call(3)
    return calls


def _profile(tmp_path, calls) -> Trace:
    """``calls`` (0-arg) each inside a ``bench.call`` span, profiled."""
    import jax

    from chipbench import trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        for call in calls:
            with bench.span("bench.call"):
                call()
    finally:
        jax.profiler.stop_trace()
    return trace.load(tmp_path)


def test_each_entry_span_holds_the_five_phases_in_order(program, tmp_path):
    tr = _profile(tmp_path, [lambda c=c: c(3) for c in program.values()])
    calls = tr.calls()
    assert len(calls) == 3
    for (lo, hi), entry in zip(calls, program):
        inside = sorted(s for s in tr.spans if s[0] >= lo and s[1] <= hi)
        entries = [s for s in inside if s[2].startswith("repro.")
                   and s[2] not in spans.PHASES + (spans.COMPILED,)]
        assert [s[2] for s in entries] == [entry]
        e0, e1, _ = entries[0]
        phases = [s for s in inside if s[2] in spans.PHASES]
        assert [s[2] for s in phases] == list(spans.PHASES)
        assert e0 <= phases[0][0] and phases[-1][1] <= e1
        for a, b in zip(phases, phases[1:]):
            assert a[1] <= b[0]  # siblings, disjoint, in order
    run = _run(tr)
    for name in READERS:
        got = _read(name, run)
        assert got is not None and math.isfinite(got), name
    assert _read("compiles.events", run) == 0


@pytest.mark.parametrize("entry", ["repro.run_sweep[xla]",
                                   "repro.cluster.what_if_sweep[market]"])
def test_a_window_that_recompiles_counts_its_compiles(program, tmp_path,
                                                      entry):
    from repro.obs.timing import compile_count
    call = program[entry]
    before = compile_count()
    same = _profile(tmp_path / "same", [lambda: call(3), lambda: call(3)])
    assert compile_count() == before
    assert _read("compiles.whatif", _run(same)) == 0
    grown = _profile(tmp_path / "grown", [lambda: call(3), lambda: call(4)])
    assert compile_count() > before
    assert _read("compiles.whatif", _run(grown)) >= 1
