"""Pallas batched-event kernel ↔ scan-engine equivalence.

Three layers of contract, strictest first:

  * kernel == ref (the generic layout machinery): the batched-event kernel
    against its pure-JAX reference on the same lane layout — bit-for-bit
    at every tile size, including lane padding.
  * engine ``impl="pallas"`` == engine ``impl="ref"`` (the same scan
    executor the kernel fuses, on the kernel's own lane layout):
    bit-for-bit identical WindowStats / MarketWindowStats across policies
    and random market configs, including the degenerate 1-pool zero-hazard
    market.
  * engine ``impl="pallas"`` vs engine ``impl="xla"`` (the production
    broadcast-nested scan executor): integer event accounting is bitwise
    identical — every admit/serve/defect/preempt decision agrees — while
    float32 window sums are asserted to a ~ulp rtol: on CPU, LLVM's
    transcendental codegen (log1p in the exponential sampler) can round an
    ulp apart between batch layouts, which is also why sub-lane tiling
    (``tile`` < lanes) gets the same soft treatment (see EXPERIMENTS.md,
    "Engine kernel: Pallas batched-event executor").

Everything runs in interpret mode (`JAX_PLATFORMS=cpu` in the CI job).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare interpreter: deterministic fallback
    from _propcheck import given, settings, st

from repro.core import (
    Exponential,
    Gamma,
    NoticeAwareKernel,
    PoolChoiceKernel,
    SingleSlotKernel,
    SpotMarket,
    SpotPool,
    ThreePhaseKernel,
    Uniform,
    run_market_sweep,
    run_sim,
    run_sweep,
)
from repro.core.engine import INT_STATS as _INT_STATS
from repro.core.waittime import DeterministicWait, ExponentialWait
from repro.kernels.sweep import batched_events, batched_event_windows_ref

LAM, MU, K = 1 / 12, 1 / 24, 10.0


def assert_stats_equal(a: dict, b: dict, context=""):
    for name, v in a.items():
        np.testing.assert_array_equal(
            np.asarray(v), np.asarray(b[name]),
            err_msg=f"{name} diverged ({context})")


# ---------------------------------------------------------------------------
# Layer 1: generic kernel == reference, every tile size, bit for bit
# ---------------------------------------------------------------------------
def _toy_step(state, stats, params):
    """Minimal event body exercising PRNG, slot selects, and mixed dtypes."""
    key, k1, k2 = jax.random.split(state["key"], 3)
    u = jax.random.uniform(k1)
    dt = jax.random.exponential(k2, dtype=jnp.float32) * params["scale"]
    iota = jax.lax.iota(jnp.int32, 8)
    slot = jnp.argmin(state["slots"])
    slots = jnp.where(iota == slot, state["slots"] + dt, state["slots"])
    return (
        {"key": key, "slots": slots},
        {"total": stats["total"] + dt,
         "hits": stats["hits"] + (u < 0.5).astype(jnp.int32)},
    )


@pytest.mark.parametrize("tile", [1, 3, 4, 64])
def test_kernel_matches_ref_all_tiles(tile):
    b = 10  # deliberately not a multiple of most tiles: exercises padding
    keys = jax.random.key_data(jax.random.split(jax.random.key(0), b))
    state = {"key": keys, "slots": jnp.zeros((b, 8), jnp.float32)}
    params = {"scale": jnp.linspace(0.5, 2.0, b)}
    zeros = {"total": jnp.zeros((), jnp.float32),
             "hits": jnp.zeros((), jnp.int32)}
    ev = (5, 12, 1)
    fs_k, st_k = batched_events(_toy_step, state, params, zeros, ev,
                                tile=tile, interpret=True)
    fs_r, st_r = batched_event_windows_ref(_toy_step, state, params, zeros,
                                           ev)
    for name in zeros:
        assert st_k[name].shape == (b, len(ev))
        np.testing.assert_array_equal(np.asarray(st_k[name]),
                                      np.asarray(st_r[name]))
    for lk, lr in zip(jax.tree.leaves(fs_k), jax.tree.leaves(fs_r)):
        np.testing.assert_array_equal(np.asarray(lk), np.asarray(lr))


# ---------------------------------------------------------------------------
# Layer 2: engine executors, bit for bit at matched lane width
# ---------------------------------------------------------------------------
ENGINE_CASES = [
    ("three_phase", Exponential(LAM), Exponential(MU), ThreePhaseKernel(),
     {"r": jnp.linspace(0.25, 4.0, 5)}),
    ("three_phase_gamma", Gamma(12.0, 1.0), Exponential(MU),
     ThreePhaseKernel(), {"r": jnp.linspace(0.0, 3.0, 4)}),
    ("single_slot", Exponential(LAM), Uniform(0.0, 48.0),
     SingleSlotKernel(wait=DeterministicWait(3.0)), {}),
    ("single_slot_exp_wait", Exponential(LAM), Exponential(MU),
     SingleSlotKernel(wait=ExponentialWait(0.5)), {}),
]


def assert_stats_close(xla: dict, pal: dict, context=""):
    """The cross-layout contract vs the production XLA executor: integer
    event accounting bitwise, float sums to ~ulp rtol."""
    for name, v in xla.items():
        if name in _INT_STATS:
            np.testing.assert_array_equal(
                np.asarray(v), np.asarray(pal[name]),
                err_msg=f"{name} diverged ({context})")
        else:
            np.testing.assert_allclose(
                np.asarray(v), np.asarray(pal[name]), rtol=1e-5,
                err_msg=f"{name} diverged ({context})")


@pytest.mark.parametrize("name,job,spot,kernel,params",
                         ENGINE_CASES, ids=[c[0] for c in ENGINE_CASES])
def test_sweep_pallas_bit_for_bit(name, job, spot, kernel, params):
    kw = dict(k=K, n_events=6_000, key=jax.random.key(7), n_seeds=3,
              rmax=8 if params else 1, chunk_events=2_048, burn_in=512)
    ref = run_sweep(job, spot, kernel, params, impl="ref", **kw)
    pal = run_sweep(job, spot, kernel, params, impl="pallas",
                    interpret=True, **kw)
    assert_stats_equal(ref, pal, name)
    assert_stats_close(run_sweep(job, spot, kernel, params, **kw), pal,
                       name)


def test_run_sim_pallas_bit_for_bit():
    kw = dict(k=K, n_events=8_000, key=jax.random.key(3), rmax=16,
              chunk_events=1_024)
    a = run_sim(Exponential(LAM), Exponential(MU), ThreePhaseKernel(),
                {"r": jnp.float32(2.5)}, **kw)
    b = run_sim(Exponential(LAM), Exponential(MU), ThreePhaseKernel(),
                {"r": jnp.float32(2.5)}, impl="pallas", interpret=True, **kw)
    assert a == b


def _market(prices, hazards, notices):
    pools = tuple(
        SpotPool(Exponential(MU / len(prices)), price=p, hazard=h, notice=n)
        for p, h, n in zip(prices, hazards, notices))
    return SpotMarket(pools=pools)


MARKET_CASES = [
    ("degenerate_1pool", SpotMarket.single(Exponential(MU)),
     ThreePhaseKernel(), {"r": jnp.linspace(0.25, 4.0, 5)}),
    ("heterogeneous_notice",
     _market((0.5, 0.3, 0.2, 0.1), (0.02, 0.05, 0.0, 0.10),
             (0.5, 0.01, 0.0, 2.0)),
     NoticeAwareKernel(checkpoint_time=0.05),
     {"r": jnp.linspace(0.25, 4.0, 4)}),
    ("pool_choice_fastest",
     _market((1.0, 0.4), (0.0, 0.08), (0.0, 0.3)),
     PoolChoiceKernel(base=ThreePhaseKernel(), choice="fastest"),
     {"r": jnp.linspace(0.5, 3.0, 3)}),
]


@pytest.mark.parametrize("name,market,kernel,params",
                         MARKET_CASES, ids=[c[0] for c in MARKET_CASES])
def test_market_sweep_pallas_bit_for_bit(name, market, kernel, params):
    kw = dict(k=K, n_events=5_000, key=jax.random.key(0), n_seeds=2,
              rmax=16, chunk_events=2_048)
    ref = run_market_sweep(Exponential(LAM), market, kernel, params,
                           impl="ref", **kw)
    pal = run_market_sweep(Exponential(LAM), market, kernel, params,
                           impl="pallas", interpret=True, **kw)
    assert_stats_equal(ref, pal, name)
    assert_stats_close(
        run_market_sweep(Exponential(LAM), market, kernel, params, **kw),
        pal, name)


@settings(max_examples=10, deadline=None)
@given(
    r_lo=st.floats(min_value=0.0, max_value=2.0),
    price=st.floats(min_value=0.05, max_value=1.0),
    hazard=st.floats(min_value=0.0, max_value=0.2),
    notice=st.floats(min_value=0.0, max_value=2.0),
    n_pools=st.integers(min_value=1, max_value=4),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_market_sweep_pallas_property(r_lo, price, hazard, notice, n_pools,
                                      seed):
    """Random market configs: pallas == ref to the last bit, xla to ints
    exactly + floats at rtol."""
    market = _market((price,) * n_pools,
                     tuple(hazard * (i % 2) for i in range(n_pools)),
                     (notice,) * n_pools)
    params = {"r": jnp.linspace(r_lo, r_lo + 2.0, 3)}
    kw = dict(k=K, n_events=2_000, key=jax.random.key(seed), n_seeds=2,
              rmax=8, chunk_events=512)
    kernel = NoticeAwareKernel(checkpoint_time=0.05)
    ref = run_market_sweep(Exponential(LAM), market, kernel, params,
                           impl="ref", **kw)
    pal = run_market_sweep(Exponential(LAM), market, kernel, params,
                           impl="pallas", interpret=True, **kw)
    assert_stats_equal(ref, pal, f"pools={n_pools} seed={seed}")
    assert_stats_close(
        run_market_sweep(Exponential(LAM), market, kernel, params, **kw),
        pal, f"pools={n_pools} seed={seed}")


# ---------------------------------------------------------------------------
# Layer 3: sub-lane tiling — ints exact, floats to ulp-level rtol
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("tile", [3, 7])
def test_sweep_pallas_small_tiles(tile):
    """Splitting lanes across kernel instances keeps every event decision
    identical (integer counters bitwise); float32 sums may pick up a few
    ulps from width-dependent CPU transcendental codegen."""
    kw = dict(k=K, n_events=4_000, key=jax.random.key(1), n_seeds=2,
              rmax=8, chunk_events=1_024)
    params = {"r": jnp.linspace(0.25, 4.0, 5)}
    xla = run_sweep(Exponential(LAM), Exponential(MU), ThreePhaseKernel(),
                    params, **kw)
    pal = run_sweep(Exponential(LAM), Exponential(MU), ThreePhaseKernel(),
                    params, impl="pallas", interpret=True, tile=tile, **kw)
    assert_stats_close(xla, pal, f"tile={tile}")


def test_unknown_impl_raises():
    with pytest.raises(ValueError, match="unknown impl"):
        run_sweep(Exponential(LAM), Exponential(MU), ThreePhaseKernel(),
                  {"r": jnp.float32(1.0)}, k=K, n_events=64,
                  key=jax.random.key(0), impl="cuda")


# ---------------------------------------------------------------------------
# Satellite: int32 order/next_seq wrap protection at window boundaries
# ---------------------------------------------------------------------------
def test_order_rebase_prevents_int32_wrap():
    """Start the engine a hair below INT32_MAX in sequence space: without
    the per-window rebase the counter wraps negative within a few chunks
    and the FIFO argmin serves newest-first; with it the shifted run is
    bitwise the run that started at zero."""
    from repro.core.engine import (WindowStats, _QueueLoop, _rebase_order,
                                   init_engine_state, run_chunked)

    job, spot, kernel = Exponential(1.0), Exponential(1.0), ThreePhaseKernel()
    rmax, chunk, n_events = 8, 128, 4_000
    params = {"r": jnp.float32(6.0)}

    @jax.jit
    def run_from(offset):
        state = init_engine_state(jax.random.key(2), job, spot, rmax)
        state = state._replace(
            order=state.order + offset * state.occ.astype(jnp.int32),
            next_seq=state.next_seq + offset)
        return run_chunked(_QueueLoop(job, spot, kernel, rmax), state,
                           params, None, jnp.float32(10.0), n_events, chunk)

    offset = jnp.int32(2**31 - 10_000)  # wraps within ~chunks without rebase
    s_hi, stats_hi = run_from(offset)
    s_lo, stats_lo = run_from(jnp.int32(0))
    for name in WindowStats._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(stats_hi, name)),
            np.asarray(getattr(stats_lo, name)), err_msg=name)
    # the rebase keeps the live counter bounded by window size + queue depth
    assert int(s_hi.next_seq) <= chunk + rmax
    assert int(s_lo.next_seq) <= chunk + rmax
    # and it is shift-invariant as a law, not just on this trajectory
    reb = _rebase_order(s_hi)
    assert int(jnp.min(jnp.where(reb.occ, reb.order, reb.next_seq))) == 0
