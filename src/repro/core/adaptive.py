"""Algorithm 1 — Adaptive Admission Control — on the market sweep engine.

The learner runs the Theorem-4 three-phase policy at the current knob ``r``,
measures the empirical average delay d(r) over a window of events, and takes
a projected gradient step on the slack penalty L(r) = ½(d(r) − δ)²:

    r ← clip(r − η·(d(r) − δ), 0, r_max)

exactly as the paper's Algorithm 1 (the sign of ∂d/∂r is absorbed into η > 0
since d(r) is increasing in r).  The event window is the engine's
:func:`repro.core.engine.run_window` over the market loop: since PR 2 the
learner runs on the **spot-market subsystem** (heterogeneous pools,
preemption with notice — :mod:`repro.core.market`), so fleets can be
trained against revocation-prone multi-pool markets on-device.  A plain
:class:`~repro.core.arrivals.ArrivalProcess` is wrapped as the degenerate
one-pool market, which reproduces the PR-1 engine bit-for-bit —
pre-market learner trajectories are unchanged.

:func:`adaptive_admission_control_batched` vmaps the whole learner over
arrays of (δ, η, η-decay, r₀, r_max, k): a fleet of learners — e.g. one per
delay target, or the paper's two far-apart initializations — advances in ONE
jitted scan instead of one Python call per learner.

Beyond-paper (recorded in EXPERIMENTS.md): an optional 1/√n step-size decay
(``eta_decay``) suppresses the stationary oscillation of constant-η SGD; and
the window statistic includes immediate on-demand dispatches (delay 0)
exactly as the paper's d(r) does.  Under preemption the window delay d(r)
averages *legs* (a checkpointed job contributes its pre-revocation wait as
one leg) — the same accounting as the host orchestrator.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arrivals import ArrivalProcess
from repro.core.engine import (_check_env, _env_params, _MarketLoop,
                               _rebase_order, run_window)
from repro.core.env import init_env_state
from repro.core.market import NoticeAwareKernel, SpotMarket, as_market
from repro.core.policies import ThreePhaseKernel
from repro.obs.timing import EntrySpan

_THREE_PHASE = ThreePhaseKernel()


def _default_kernel(market: SpotMarket):
    """Legacy kernel on the degenerate market (bit-for-bit with PR 1);
    notice-aware three-phase everywhere else."""
    if market.n_pools == 1 and not market.preemptible:
        return _THREE_PHASE
    return NoticeAwareKernel()


class AdaptiveTrace(NamedTuple):
    """Per-window trajectory (stacked over windows)."""

    r: jax.Array  # knob before the window's update
    window_delay: jax.Array  # d(r) measured in the window
    window_cost: jax.Array  # average cost of jobs completed in the window
    jobs: jax.Array
    completed: jax.Array
    spot_served: jax.Array
    cost_sum: jax.Array
    delay_sum: jax.Array
    time: jax.Array
    spot_arrivals: jax.Array
    spot_found_empty: jax.Array
    preemptions: jax.Array
    resumed: jax.Array


def _adaptive_core(job, market, kernel, rmax, window_events, n_windows,
                   k_cost, delta, eta, eta_decay, r0, r_max, key, ep=None,
                   max_step=None, shock_reset=False):
    """One learner's full trajectory (vmap-able over every traced arg).

    ``ep`` threads the environment-timeline axis through every window
    (non-stationary prices/hazards/availability); ``max_step`` clamps the
    per-window knob excursion and zeroes non-finite updates (poisoned
    windows can't fling ``r``); ``shock_reset`` restarts the knob at
    ``r0`` whenever a window crosses into a shock segment.  All three
    default off, compiling the identical pre-env program.
    """
    mp = market.params()
    loop = _MarketLoop(job, market, kernel, rmax, market.preemptible)
    state0 = loop.init(key, mp, False, ep)
    if ep is not None:
        state0 = (state0, init_env_state(ep))

    def outer(sc, idx):
        state, r = sc
        state, s = run_window(loop, state, {"r": r}, mp, k_cost,
                              window_events, ep=ep)
        if ep is not None:
            s, es = s
        # learner horizons are unbounded (windows × events); rebase the
        # int32 join-sequence counters every window so they never wrap
        state = _rebase_order(state)
        completed = jnp.maximum(s.jobs_completed, 1).astype(jnp.float32)
        d = s.delay_sum / completed
        c = s.cost_sum / completed
        step = eta / jnp.sqrt(1.0 + eta_decay * idx.astype(jnp.float32))
        upd = step * (d - delta)
        if max_step is not None:
            # guardrail: bound the excursion; a poisoned window (NaN/inf
            # delay) contributes a zero step instead of destroying r
            upd = jnp.clip(upd, -max_step, max_step)
            upd = jnp.where(jnp.isfinite(upd), upd, 0.0)
        r_new = jnp.clip(r - upd, 0.0, r_max)
        if shock_reset and ep is not None:
            # regime flip: the learned knob is stale under a new supply
            # regime — restart from r0 when the window entered a shock
            flipped = (es.storms_entered + es.blackouts_entered
                       + es.spikes_entered) > 0
            r_new = jnp.where(flipped, jnp.asarray(r0, jnp.float32), r_new)
        trace = AdaptiveTrace(
            r=r,
            window_delay=d,
            window_cost=c,
            jobs=s.jobs_arrived,
            completed=s.jobs_completed,
            spot_served=s.spot_served,
            cost_sum=s.cost_sum,
            delay_sum=s.delay_sum,
            time=s.time_elapsed,
            spot_arrivals=s.spot_arrivals,
            spot_found_empty=s.spot_found_empty,
            preemptions=jnp.sum(s.pool_preempted),
            resumed=s.resumed,
        )
        return (state, r_new), trace

    (_, r_final), traces = jax.lax.scan(
        outer, (state0, jnp.float32(r0)), jnp.arange(n_windows)
    )
    return r_final, traces


@functools.partial(
    jax.jit,
    static_argnames=("job", "market", "kernel", "rmax", "window_events",
                     "n_windows", "max_step", "shock_reset"),
)
def _adaptive_jit(job, market, kernel, rmax, window_events, n_windows,
                  k_cost, delta, eta, eta_decay, r0, r_max, key, ep=None,
                  max_step=None, shock_reset=False):
    return _adaptive_core(job, market, kernel, rmax, window_events,
                          n_windows, k_cost, delta, eta, eta_decay, r0,
                          r_max, key, ep=ep, max_step=max_step,
                          shock_reset=shock_reset)


@functools.partial(
    jax.jit,
    static_argnames=("job", "market", "kernel", "rmax", "window_events",
                     "n_windows", "max_step", "shock_reset"),
)
def _adaptive_batched_jit(job, market, kernel, rmax, window_events,
                          n_windows, k_cost, delta, eta, eta_decay, r0,
                          r_max, keys, ep=None, max_step=None,
                          shock_reset=False):
    one = functools.partial(_adaptive_core, job, market, kernel, rmax,
                            window_events, n_windows)

    def learner(kc, de, et, ed, r0_, rm, ky):
        # ep and the guardrail knobs are shared across the fleet (closed
        # over, not vmapped)
        return one(kc, de, et, ed, r0_, rm, ky, ep=ep, max_step=max_step,
                   shock_reset=shock_reset)

    return jax.vmap(learner)(k_cost, delta, eta, eta_decay, r0, r_max, keys)


def _assemble(tr, r_final) -> dict:
    """Host-side float64 running averages from a (stacked) trace.

    Works for a single learner (arrays shaped ``(n_windows,)``) and a batch
    (arrays ``(batch, n_windows)``): the window axis is the last one.
    """
    t = jax.tree.map(lambda x: np.asarray(x, np.float64), tr)
    cum_completed = np.maximum(np.cumsum(t.completed, axis=-1), 1.0)
    running_cost = np.cumsum(t.cost_sum, axis=-1) / cum_completed
    running_delay = np.cumsum(t.delay_sum, axis=-1) / cum_completed
    spot_arr = np.maximum(np.cumsum(t.spot_arrivals, axis=-1), 1.0)
    pi0_spot = np.cumsum(t.spot_found_empty, axis=-1) / spot_arr
    r_star = np.asarray(r_final, np.float64)
    return {
        "r": t.r,
        "r_star": r_star if r_star.ndim else float(r_star),
        "window_delay": t.window_delay,
        "window_cost": t.window_cost,
        "running_cost": running_cost,
        "running_delay": running_delay,
        "pi0_spot": pi0_spot,
        "final_cost": _last(running_cost),
        "final_delay": _last(running_delay),
        "final_pi0": _last(pi0_spot),
        "jobs_total": _reduce(np.sum, t.jobs),
        "time_total": _reduce(np.sum, t.time),
        "preemptions_total": _reduce(np.sum, t.preemptions),
        "resumed_total": _reduce(np.sum, t.resumed),
    }


def _last(x: np.ndarray):
    v = x[..., -1]
    return float(v) if v.ndim == 0 else v


def _reduce(fn, x: np.ndarray):
    v = fn(x, axis=-1)
    return float(v) if v.ndim == 0 else v


def adaptive_admission_control(
    job: ArrivalProcess,
    spot,
    *,
    k: float = 10.0,
    delta: float,
    eta: float = 0.05,
    eta_decay: float = 0.0,
    r0: float = 0.0,
    r_max: float = 16.0,
    window_events: int = 2048,
    n_windows: int = 400,
    rmax_slots: int = 64,
    key: jax.Array,
    kernel=None,
    env=None,
    max_step: float | None = None,
    shock_reset: bool = False,
) -> dict:
    """Run Algorithm 1; return the trajectory and running averages (float64).

    ``spot`` may be a plain :class:`ArrivalProcess` (degenerate one-pool
    market — PR-1 behaviour, bit-for-bit) or a :class:`SpotMarket` to train
    the learner against heterogeneous pools and preemption-with-notice.
    ``kernel`` overrides the policy kernel (default: shared three-phase on a
    degenerate market, :class:`NoticeAwareKernel` otherwise); it must read
    the knob from ``params["r"]``.

    Robustness knobs (all off by default, compiling the identical
    program): ``env`` trains against a non-stationary
    :class:`repro.core.env.EnvTimeline`; ``max_step`` clamps each window's
    knob update to ``±max_step`` and zeroes non-finite updates;
    ``shock_reset`` restarts the knob at ``r0`` whenever a window enters a
    storm/blackout/spike segment.

    Returns a dict with per-window arrays: ``r`` (knob), ``window_delay``,
    ``window_cost``, and running averages ``running_cost`` / ``running_delay``
    (cumulative, matching the paper's C(r(n)) and d(r(n)) plots), plus the
    final knob ``r_star`` and Theorem-1 cross-check fields.
    """
    with EntrySpan("repro.adaptive_admission_control") as call:
        market = as_market(spot)
        kernel = _default_kernel(market) if kernel is None else kernel
        _check_env(env)
        ep = _env_params(env, market.n_pools)
        call.phase("dispatch")
        r_final, tr = _adaptive_jit(
            job, market, kernel, rmax_slots, window_events, n_windows,
            jnp.float32(k), jnp.float32(delta), jnp.float32(eta),
            jnp.float32(eta_decay), jnp.float32(r0), jnp.float32(r_max),
            key, ep=ep,
            max_step=None if max_step is None else float(max_step),
            shock_reset=bool(shock_reset),
        )
        r_final, tr = call.to_host((r_final, tr))
        return _assemble(tr, r_final)


def adaptive_admission_control_batched(
    job: ArrivalProcess,
    spot,
    *,
    k: float = 10.0,
    delta,
    eta=0.05,
    eta_decay=0.0,
    r0=0.0,
    r_max=16.0,
    window_events: int = 2048,
    n_windows: int = 400,
    rmax_slots: int = 64,
    key: jax.Array,
    independent_keys: bool = False,
    kernel=None,
    env=None,
    max_step: float | None = None,
    shock_reset: bool = False,
) -> dict:
    """Run a fleet of Algorithm-1 learners in ONE jitted scan.

    ``delta``/``eta``/``eta_decay``/``r0``/``r_max``/``k`` broadcast to a
    common 1-D batch shape — e.g. ``delta=jnp.linspace(2, 30, 16)`` trains 16
    multi-δ learners at once, or ``r0=jnp.array([0.05, 4.0])`` reproduces the
    paper's two-initialization convergence plots in a single call.  By
    default every learner sees the same event stream (common random numbers,
    so trajectories differ only through the policy); pass
    ``independent_keys=True`` to fold a per-learner offset into the key.
    ``spot`` may be a :class:`SpotMarket` (see
    :func:`adaptive_admission_control`) to train the fleet on a preemptible
    multi-pool market.

    Returns the same dict as :func:`adaptive_admission_control` with a
    leading batch axis on every array (and on the ``final_*``/``r_star``
    scalars).
    """
    with EntrySpan("repro.adaptive_admission_control_batched") as call:
        market = as_market(spot)
        kernel = _default_kernel(market) if kernel is None else kernel
        _check_env(env)
        ep = _env_params(env, market.n_pools)
        args = [jnp.asarray(x, jnp.float32)
                for x in (k, delta, eta, eta_decay, r0, r_max)]
        batch = jnp.broadcast_shapes(*(a.shape for a in args), (1,))
        n = int(np.prod(batch))
        args = [jnp.broadcast_to(a, batch).reshape(-1) for a in args]
        keys = (jax.random.split(key, n) if independent_keys
                else jnp.repeat(key[None], n, axis=0))
        call.phase("dispatch")
        r_final, tr = _adaptive_batched_jit(
            job, market, kernel, rmax_slots, window_events, n_windows,
            *args, keys, ep=ep,
            max_step=None if max_step is None else float(max_step),
            shock_reset=bool(shock_reset),
        )
        r_final, tr = call.to_host((r_final, tr))
        # restore multi-dimensional batch shapes (e.g. a delta × r0 meshgrid)
        r_final = r_final.reshape(batch)
        tr = jax.tree.map(lambda x: x.reshape(batch + x.shape[1:]), tr)
        return _assemble(tr, r_final)
