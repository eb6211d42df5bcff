"""The entry points' phase spans, the compile listener and the
device-side names (``repro.obs.timing.EntrySpan``).

The spans and the compile count are read back from a real profile in
``chipbench/tests/test_chipbench_spans.py``; here: the phases' order and
joining, that fetching the stats in one ``jax.device_get`` leaves every
answer bit for bit, that importing registers no listener, and that the
lowered executors carry the kernel's and the glue's names.
"""
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import engine as E
from repro.core.arrivals import Exponential
from repro.core.market import NoticeAwareKernel, SpotMarket, SpotPool
from repro.core.policies import ThreePhaseKernel
from repro.core.regions import Region, RegionTopology, RoutingKernel
from repro.obs import Telemetry, timing

LAM, MU = 1 / 12, 1 / 24
MARKET = SpotMarket(pools=(
    SpotPool(Exponential(MU / 2), price=0.5, hazard=0.02, notice=0.5),
    SpotPool(Exponential(MU / 2), price=0.2, hazard=0.05, notice=0.01),
))
TOPOLOGY = RegionTopology(regions=(
    Region(job=Exponential(1 / 24), spot=Exponential(1 / 48), price=0.9),
    Region(job=Exponential(1 / 24), spot=Exponential(1 / 48), price=0.2),
))
RS = {"r": jnp.linspace(0.5, 3.0, 3)}


def _sweeps():
    """(loop, entry-point call, its summarize) at a tiny size."""
    kw = dict(n_events=600, key=jax.random.key(3), n_seeds=2)
    return {
        "single": (lambda: E.run_sweep(
            Exponential(LAM), Exponential(MU), ThreePhaseKernel(), RS,
            rmax=8, **kw),
            E.summarize, None),
        "market": (lambda: E.run_market_sweep(
            Exponential(LAM), MARKET, NoticeAwareKernel(checkpoint_time=0.05),
            RS, rmax=8, telemetry=Telemetry(), **kw),
            E.summarize_market, Telemetry()),
        "region": (lambda: E.run_region_sweep(
            TOPOLOGY, RoutingKernel(ThreePhaseKernel(), choice="cheapest"),
            RS, **kw),
            E.summarize_region, None),
    }


def _assert_same(a, b):
    assert a.keys() == b.keys()
    for name in a:
        if isinstance(a[name], dict):
            _assert_same(a[name], b[name])
        else:
            x, y = np.asarray(a[name]), np.asarray(b[name])
            assert x.dtype == y.dtype and x.shape == y.shape, name
            assert np.array_equal(x, y, equal_nan=True), name


@pytest.mark.parametrize("loop", ["single", "market", "region"])
def test_summarize_of_the_fetched_stats_is_bit_identical(loop, monkeypatch):
    """``to_host`` copies the stats once with ``jax.device_get``; the
    float64 sums read the same float32 values as from the device."""
    call, summarize, tel = _sweeps()[loop]
    seen = []
    to_host = timing.EntrySpan.to_host

    def spy(self, stats):
        seen.append(stats)
        return to_host(self, stats)

    monkeypatch.setattr(timing.EntrySpan, "to_host", spy)
    out = call()
    (device,) = seen
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(device))
    host = jax.device_get(device)
    assert not any(isinstance(x, jax.Array) for x in jax.tree.leaves(host))
    on_device = summarize(device, tel)
    _assert_same(on_device, summarize(host, tel))
    _assert_same(out, E._reshape_sweep(on_device, (3,), 2))


def _names(monkeypatch):
    """The spans the entry points open, as ``+name`` / ``-name``."""
    log = []

    class Span:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            log.append("+" + self.name)

        def __exit__(self, *exc):
            log.append("-" + self.name)

    monkeypatch.setattr(timing, "annotate", Span)
    return log


def test_phases_are_siblings_in_order(monkeypatch):
    log = _names(monkeypatch)
    _sweeps()["single"][0]()
    entry = "repro.run_sweep[xla]"
    phases = [f"repro.{p}" for p in timing.PHASES]
    expect = ["+" + entry]
    for p in phases:
        expect += ["+" + p, "-" + p]
    assert log == expect + ["-" + entry]


def test_a_nested_entry_point_joins_the_outer_call(monkeypatch):
    log = _names(monkeypatch)
    with timing.EntrySpan("outer") as call:
        assert _sweeps()["single"][0]()["avg_cost"].shape == (3, 2)
        assert call.name == "outer"
    assert [n for n in log if n.startswith("+")] == (
        ["+outer"] + [f"+repro.{p}" for p in timing.PHASES])
    assert log[-1] == "-outer"


def test_an_entry_point_that_raises_closes_its_spans(monkeypatch):
    log = _names(monkeypatch)
    with pytest.raises(ValueError, match="unknown impl"):
        E.run_sweep(Exponential(LAM), Exponential(MU), ThreePhaseKernel(),
                    RS, n_events=100, key=jax.random.key(0), impl="nope")
    assert log[-2:] == ["-repro.dispatch", "-repro.run_sweep[nope]"]
    log.clear()
    _sweeps()["single"][0]()  # a fresh call, not joined to the dead one
    assert log[0] == "+repro.run_sweep[xla]" and log[-1] == log[0].replace(
        "+", "-")
    with pytest.raises(ValueError, match="unknown phase"):
        with timing.EntrySpan("x") as call:
            call.phase("compile")


def test_importing_the_library_registers_no_listener():
    code = ("import repro.core, repro.cluster.orchestrator\n"
            "from repro.obs import timing\n"
            "assert not timing._listening, 'listener at import'\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr


def test_lowered_executors_carry_the_kernel_and_glue_names():
    """Six lanes in tiles of four: the slab keys, the padding and the
    unpacking are all in the program, under ``repro.glue.*`` scopes, and
    the kernel is ``repro_batched_events``."""
    k = jnp.full((3,), 10.0)
    keys = jax.random.key_data(jax.random.split(jax.random.key(0), 2))
    kw = dict(executor="pallas", rng="slab")
    lowered = {
        "single": E._run_sweep_pallas_jit.lower(
            Exponential(LAM), Exponential(MU), ThreePhaseKernel(), 8, 300,
            100, 50, 4, True, RS, k, keys, **kw),
        "market": E._run_market_sweep_pallas_jit.lower(
            Exponential(LAM), MARKET, NoticeAwareKernel(checkpoint_time=0.05),
            8, MARKET.preemptible, 300, 100, 50, 4, True, RS,
            jax.tree.map(lambda a: jnp.broadcast_to(a, (3,) + np.shape(a)),
                         MARKET.params()), k, keys, **kw),
    }
    for loop, low in lowered.items():
        text = low.as_text(debug_info=True)
        for name in ("repro.glue.lane_slabs", "repro.glue.pad_lanes",
                     "repro.glue.lanes_first", "repro_batched_events"):
            assert name in text, (loop, name)
