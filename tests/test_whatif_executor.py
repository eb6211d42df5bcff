"""The what-if's executor: :meth:`SpotCluster.what_if_sweep` picks it from
the backend and always draws the slab stream.

On a TPU the answer comes from the compiled batched-event kernel
(``impl="pallas", interpret=False, rng="slab"``); anywhere else from the
XLA scan on the same stream (``impl="xla", rng="slab"``).  Checked here,
on the CPU:

* the executor contract at the what-if's own shape (16 r x 2 seeds = 32
  lanes, one window, a tile that covers every lane): the kernel, run
  through its interpreter, against the what-if's answer — integer stats
  bitwise, floats to rtol 1e-5;
* the law is the one the split stream answered with: per-r on-demand
  counts and average cost pass a two-sample KS test against
  ``run_market_sweep(rng="split")``;
* every variant still answers: ``telemetry=``, and ``shard="lanes"`` on
  one device and on two forced host devices;
* on a TPU backend the what-if asks for the compiled kernel.

tests/test_tpu_compile.py compiles what the what-if runs on a TPU for a
described v5e.
"""
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _stats import assert_same_distribution, assert_stats_close

from repro.cluster import orchestrator
from repro.cluster.orchestrator import OnlineAdmissionController, SpotCluster
from repro.core import engine as E
from repro.core.arrivals import Exponential
from repro.core.market import NoticeAwareKernel, SpotMarket, SpotPool
from repro.obs import TEL_INT_STATS, Telemetry

LAM, MU, K = 1 / 12, 1 / 24, 10.0
CHECKPOINT = 0.025
RS = np.linspace(0.5, 8.0, 16)  # 16 r x 2 seeds: the what-if's 32 lanes

# four pools at the fig. 2 total spot rate, two-minute notice, a 90 s
# checkpoint; hazards high enough that short runs revoke and resume
MARKET = SpotMarket(pools=tuple(
    SpotPool(Exponential(MU / 4), price=p, hazard=h, notice=0.0333)
    for p, h in ((1.0, 0.05), (2.0, 0.03), (3.0, 0.02), (4.0, 0.01))))


def _cluster():
    return SpotCluster(job_process=Exponential(LAM), market=MARKET,
                       k_cost=K,
                       controller=OnlineAdmissionController(delta=12.0),
                       checkpoint_hours=CHECKPOINT)


def _market_sweep(rs, **kw):
    """``run_market_sweep`` on the what-if's market, kernel and grid."""
    return E.run_market_sweep(
        Exponential(LAM), MARKET, NoticeAwareKernel(checkpoint_time=CHECKPOINT),
        {"r": jnp.asarray(rs, jnp.float32)}, k=K, **kw)


def test_off_tpu_the_what_if_runs_the_xla_scan_on_the_slab_stream():
    assert orchestrator._what_if_executor() == {"impl": "xla", "rng": "slab"}


def test_on_a_tpu_the_what_if_asks_for_the_compiled_kernel(monkeypatch):
    import repro.kernels.sweep.ops as ops
    seen = {}

    def fake(*args, **kw):
        seen.update(kw)
        return {}

    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    monkeypatch.setattr(E, "run_market_sweep", fake)
    for variant in ({}, {"telemetry": Telemetry()}, {"shard": "lanes"}):
        seen.clear()
        _cluster().what_if_sweep(RS, key=jax.random.key(0), **variant)
        assert (seen["impl"], seen["interpret"], seen["rng"]) == (
            "pallas", False, "slab"), variant
        assert "tile" not in seen and "chunk_events" not in seen


def test_kernel_matches_the_what_if_at_its_own_shape():
    """Interpreted kernel vs the what-if's answer: 32 lanes, one window,
    the engine's default tile clamped to every lane."""
    key, n_events = jax.random.key(2024), 2_000
    answer = _cluster().what_if_sweep(RS, n_events=n_events, key=key)
    kernel = _market_sweep(RS, n_events=n_events, key=key, n_seeds=2,
                           impl="pallas", interpret=True, rng="slab")
    assert set(answer) == set(kernel)
    assert np.asarray(answer["ondemand"]).shape == (16, 2)
    assert int(np.sum(answer["resumed"])) > 0  # the notice path ran
    assert_stats_close(answer, kernel, "what-if vs interpreted kernel")


@pytest.mark.parametrize("seed", [11, 4242])
def test_the_what_ifs_law_is_the_split_streams(seed):
    """Per r: on-demand counts and average cost over 64 seeds, the
    what-if's slab stream against the split stream, KS at 1e-4."""
    rs, n_seeds, n_events = np.array([0.5, 1.5, 3.0, 6.0]), 64, 2_000
    slab = _cluster().what_if_sweep(rs, n_events=n_events, n_seeds=n_seeds,
                                    key=jax.random.key(seed))
    split = _market_sweep(rs, n_events=n_events, n_seeds=n_seeds,
                          key=jax.random.key(seed + 77_777), rng="split")
    for name in ("ondemand", "avg_cost"):
        a, b = np.asarray(slab[name]), np.asarray(split[name])
        assert a.shape == b.shape == (len(rs), n_seeds)
        for i, r in enumerate(rs):
            assert_same_distribution(a[i], b[i], name=f"{name} r={r}")


def test_the_what_if_answers_with_telemetry():
    key = jax.random.key(3)
    cluster = _cluster()
    tel = cluster.what_if_sweep(RS, n_events=1_000, key=key,
                                telemetry=Telemetry())
    base = cluster.what_if_sweep(RS, n_events=1_000, key=key)
    assert set(base) < set(tel)
    for name in base:  # telemetry rides along without moving a stat
        np.testing.assert_array_equal(np.asarray(tel[name]),
                                      np.asarray(base[name]), err_msg=name)
    for name in TEL_INT_STATS:
        if name in tel:
            assert np.asarray(tel[name]).shape[:2] == (16, 2), name
    assert int(np.sum(tel["rejects"])) > 0


def test_the_what_if_answers_sharded_on_one_device():
    key = jax.random.key(4)
    cluster = _cluster()
    sharded = cluster.what_if_sweep(RS, n_events=1_000, key=key,
                                    shard="lanes")
    assert_stats_close(cluster.what_if_sweep(RS, n_events=1_000, key=key),
                       sharded, "what-if shard='lanes' @ default mesh")


def test_the_what_if_answers_sharded_on_two_host_devices():
    """15 r x 2 seeds = 30 lanes over 2 forced host devices, telemetry
    on: integer stats bitwise against the unsharded what-if."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import sys; sys.path.insert(0, "src")
        import jax, numpy as np
        from repro.cluster.orchestrator import (OnlineAdmissionController,
                                                SpotCluster)
        from repro.core.arrivals import Exponential
        from repro.core.engine import INT_STATS
        from repro.core.market import SpotMarket, SpotPool
        from repro.distributed.sharding import lane_mesh
        from repro.obs import TEL_INT_STATS, Telemetry

        assert len(jax.devices()) == 2, jax.devices()
        market = SpotMarket(pools=(
            SpotPool(Exponential(1 / 48), price=1.0, hazard=0.05,
                     notice=0.0333),
            SpotPool(Exponential(1 / 48), price=2.0, hazard=0.02,
                     notice=0.0333)))
        cluster = SpotCluster(job_process=Exponential(1 / 12), market=market,
                              k_cost=10.0,
                              controller=OnlineAdmissionController(delta=12.0),
                              checkpoint_hours=0.025)
        kw = dict(n_events=1_000, key=jax.random.key(5),
                  telemetry=Telemetry())
        rs = np.linspace(0.5, 8.0, 15)
        a = cluster.what_if_sweep(rs, **kw)
        b = cluster.what_if_sweep(rs, shard="lanes", mesh=lane_mesh(2), **kw)
        assert set(a) == set(b)
        for name in a:
            x, y = np.asarray(a[name]), np.asarray(b[name])
            if name in INT_STATS or name in TEL_INT_STATS \\
                    or np.issubdtype(x.dtype, np.integer):
                np.testing.assert_array_equal(x, y, err_msg=name)
            else:
                np.testing.assert_allclose(x, y, rtol=1e-5, err_msg=name)
        print("OK")
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=".", timeout=600)
    assert "OK" in out.stdout, (out.stdout[-1000:], out.stderr[-3000:])
