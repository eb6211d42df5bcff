"""The reduction from a profiler trace to the per-layer metrics."""
from __future__ import annotations

import pytest

from chipbench_testlib import ROOT

from chipbench import bench
from chipbench.trace import Trace, merge

METRICS = ROOT / "chipbench" / "metrics"


def _read(name, run):
    return bench.load_module(METRICS / f"{name}.py").read(run)


def _synthetic() -> Trace:
    """Two devices, two calls of 10 s: a kernel of 6 s a call, glue ops
    that overlap on device 1, and an idle gap around the calls' seam.
    Busy: device 0 [1, 8] + [12, 19] = 14 s, device 1 [1, 8.5] + [13, 19]
    = 13.5 s, of which the kernel is 12 s on each."""
    k, g = "custom-call", "fusion"
    ops = {
        "/device:TPU:0": [[1.0, 2.0, "keys", g], [2.0, 8.0, "sweep", k],
                          [12.0, 13.0, "keys", g], [13.0, 19.0, "sweep", k]],
        "/device:TPU:1": [[1.0, 2.0, "keys", g], [1.5, 2.5, "pad", g],
                          [2.5, 8.5, "sweep", k], [13.0, 19.0, "sweep", k]],
    }
    spans = [[0.0, 10.0, "bench.call"], [10.0, 20.0, "bench.call"],
             [9.5, 10.5, "summarize"], [-5.0, 0.0, "bench.warmup"]]
    return Trace(ops, spans)


def test_union_of_intervals():
    assert merge([(3, 4), (0, 1), (0.5, 2), (2, 2.5), (5, 5)]) == [
        [0, 2.5], [3, 4]]


def test_busy_window_and_idle_on_a_synthetic_trace():
    tr = _synthetic()
    assert tr.window() == (0.0, 20.0)
    assert tr.busy_s() == pytest.approx(13.75)
    run = bench.Run(setup_s=1.0, lane_events_per_call=1000,
                    calls=[(0.0, 10.0), (10.0, 20.0)], answers=[{}, {}],
                    trace=tr)
    assert _read("kernel_ns_per_event", run) == pytest.approx(24.0 * 1e9
                                                              / 2000)
    assert _read("kernel_ns_per_event.whatif", run) == pytest.approx(
        24.0 * 1e9 / 2000)
    assert _read("glue_busy_pct", run) == pytest.approx(
        100 * (13.75 - 12.0) / 13.75)
    assert _read("device_idle_pct.events", run) == pytest.approx(
        100 * (1 - 13.75 / 20))
    assert _read("device_ms_per_answer", run) == pytest.approx(1e3 * 13.75
                                                               / 2)
    assert _read("host_ms_per_answer", run) == pytest.approx(
        1e3 * (20 - 13.75) / 2)
    assert _read("events_per_s", run) is None  # a traced run: no rates
    bd = tr.breakdown()
    assert bd["device_ops"][0] == ["sweep", 24.0]
    assert len(bd["idle_gaps"]) <= 10
    # device 1 idles 8.5-13 s inside the second call; device 0 idles
    # 8-12 s, and at its middle the host runs the 1 s "summarize" span
    assert bd["idle_gaps"][:2] == [["bench.call", 4.5], ["summarize", 4.0]]


def test_a_reader_finds_nothing_without_a_kernel_or_a_trace():
    tr = Trace({"/device:TPU:0": [[0.0, 1.0, "fusion", "fusion"]]},
               [[0.0, 2.0, "bench.call"]])
    run = bench.Run(setup_s=1.0, lane_events_per_call=10,
                    calls=[(0.0, 2.0)], answers=[{}], trace=tr)
    assert _read("kernel_ns_per_event", run) is None
    assert _read("kernel_ns_per_event.whatif", run) is None
    assert _read("glue_busy_pct", run) is None
    run.trace = None
    for name in ("kernel_ns_per_event", "kernel_ns_per_event.whatif",
                 "device_idle_pct.events",
                 "device_ms_per_answer", "host_ms_per_answer"):
        assert _read(name, run) is None


def test_a_metric_that_finds_nothing_fails_the_run():
    """A cell's per-layer metric that finds no kernel in the trace is no
    metric left out but a run that fails."""
    bench_json = {"per_layer": [{"name": "kernel_ns_per_event",
                                 "unit": "ns/event",
                                 "workloads": ["fig2.deep"]}]}
    tr = Trace({"/device:TPU:0": [[0.0, 1.0, "fusion", "fusion"]]},
               [[0.0, 2.0, "bench.call"]])
    run = bench.Run(setup_s=1.0, lane_events_per_call=10,
                    calls=[(0.0, 2.0)], answers=[{}], trace=tr)
    with pytest.raises(bench.MissingMetric, match="kernel_ns_per_event"):
        bench.read_metrics(bench_json, "fig2.deep", "per_layer", run)
    assert bench.read_metrics(bench_json, "market4.whatif", "per_layer",
                              run) == {}
