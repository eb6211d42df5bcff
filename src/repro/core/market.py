"""SpotMarket — heterogeneous spot pools with preemption-with-notice.

The paper (and PR 1's engine) models ONE spot arrival process and never
revokes work.  Real spot markets are many *pools* (instance type × zone)
with distinct prices and availability, and instances are reclaimed with an
advance-notice window.  This module is the static descriptor layer of the
on-device market subsystem:

  * :class:`SpotPool`   — one pool: traced arrival process, price ``c_p``,
    preemption hazard ``h_p`` (Poisson revocation clock), notice window.
  * :class:`SpotMarket` — a static, hashable tuple of pools.  The engine
    (:mod:`repro.core.engine`) carries a small *vector* of per-pool
    ``next_spot``/``next_preempt`` clocks merged into its renewal event loop;
    pool events join the existing spot > deadline > job tie order (preempt
    slots in after spot: spot > preempt > deadline > job).
  * market policy kernels — the engine protocol gains a pool-choice hook::

        admit_market(params, qlen, pool_state, key) -> (admit?, budget, pool)

    plus a preemption hook consulted when a pool revokes a running job::

        on_preempt(params, age, notice, qlen, key) -> resume?

    Legacy two-tuple kernels (``admit(params, qlen, key)``) still work —
    the engine routes them to pool 0 and defects on preemption, which is
    exactly the degenerate market.
  * :func:`checkpoint_within_notice` — the one notice law, shared by the
    traced :class:`NoticeAwareKernel` and the host cluster orchestrator
    (same dual host/traced backend pattern as ``three_phase_admit_prob``).

Model semantics (recorded in EXPERIMENTS.md):

  * A queued job tagged pool ``p`` *is running on a pool-p spot instance*;
    the pool's spot event is its service completion (cost ``c_p``).
  * Pool ``p``'s preempt event revokes the FIFO-oldest pool-p job (the
    longest-running instance).  The partial leg is paid (``c_p``), then the
    kernel decides: **checkpoint within the notice window and re-enter the
    queue** (age resets, the job re-joins FIFO order on the same pool — the
    orchestrator's leg accounting) or **defect to on-demand** (cost ``k``,
    delay = its age).  A zero-hazard pool never fires; its clock stays at
    INF and the engine statically removes the whole preemption path, which
    is how the degenerate 1-pool market reproduces the PR-1 engine
    bit-for-bit.
  * Per-pool PRNG streams are keyed by ``fold_in(key, pool.tag)`` — a
    *label-independent* identity — so relabeling (permuting) pools with
    their tags leaves every sampled stream, and therefore π₀ and the cost
    accounting, exactly invariant (tie-breaks between pools are by position
    but ties are measure-zero for continuous samplers).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Protocol, Sequence, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arrivals import ArrivalProcess
from repro.core.clocks import (argmax_first, argmin_first, choice_cols,
                               gumbel_from_u, kernel_slab_cols)
from repro.core.policies import three_phase_admit_prob

_INF = np.float32(3e38)  # np scalar: inlines as a literal in kernel traces


# ---------------------------------------------------------------------------
# Descriptors
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SpotPool:
    """One spot pool: arrival process + price + preemption hazard/notice.

    ``tag`` is the pool's stable PRNG-stream identity (defaults to its index
    in the market); keep tags fixed when permuting pools to get bitwise
    relabel-invariance.
    """

    arrival: ArrivalProcess
    price: float = 1.0
    hazard: float = 0.0  # preemption events per unit time on the running job
    notice: float = 0.0  # advance-notice window length
    tag: int | None = None

    def rate(self) -> float:
        return self.arrival.rate()


@dataclasses.dataclass(frozen=True)
class SpotMarket:
    """P heterogeneous spot pools as one static, hashable descriptor."""

    pools: tuple[SpotPool, ...]

    def __post_init__(self):
        if not self.pools:
            raise ValueError("a SpotMarket needs at least one pool")
        tagged = tuple(
            dataclasses.replace(p, tag=i) if p.tag is None else p
            for i, p in enumerate(self.pools)
        )
        tags = [p.tag for p in tagged]
        if len(set(tags)) != len(tags):
            raise ValueError(f"pool tags must be unique, got {tags}")
        object.__setattr__(self, "pools", tagged)

    # ------------------------------------------------------------- structure
    @property
    def n_pools(self) -> int:
        return len(self.pools)

    @property
    def preemptible(self) -> bool:
        """Static: does any pool carry a preemption hazard?"""
        return any(p.hazard > 0.0 for p in self.pools)

    @property
    def is_degenerate(self) -> bool:
        """1 pool, unit price, zero hazard — the PR-1 engine, bit-for-bit."""
        p = self.pools[0]
        return self.n_pools == 1 and p.hazard == 0.0 and p.price == 1.0

    # ------------------------------------------------------------ host views
    def prices(self) -> np.ndarray:
        return np.array([p.price for p in self.pools], np.float64)

    def hazards(self) -> np.ndarray:
        return np.array([p.hazard for p in self.pools], np.float64)

    def notices(self) -> np.ndarray:
        return np.array([p.notice for p in self.pools], np.float64)

    def rates(self) -> np.ndarray:
        return np.array([p.rate() for p in self.pools], np.float64)

    def total_rate(self) -> float:
        return float(self.rates().sum())

    # --------------------------------------------------------- traced params
    def params(self) -> dict:
        """Traced pools-config pytree consumed by the engine event loop.

        ``spot_scale`` multiplies pool inter-arrival times (scale > 1 =
        scarcer slots) — a distribution-generic availability axis that a
        sweep can trace without retracing the arrival family.  ``rate`` is
        the raw (unscaled) per-pool slot rate; it rides in the traced
        params rather than being materialized inside the event body so the
        body stays constant-capture-free under the Pallas kernel trace.
        """
        return {name: jnp.asarray(v) for name, v in self.host_params().items()}

    def host_params(self) -> dict:
        """:meth:`params` as float32 NumPy arrays, bitwise the same values:
        an entry point hands them to its one jitted call as operands,
        building no device array of its own."""
        return {
            "price": self.prices().astype(np.float32),
            "hazard": self.hazards().astype(np.float32),
            "notice": self.notices().astype(np.float32),
            "spot_scale": np.ones((self.n_pools,), np.float32),
            "rate": self.rates().astype(np.float32),
        }

    # ------------------------------------------------------------- utilities
    @staticmethod
    def single(spot: ArrivalProcess, *, price: float = 1.0,
               hazard: float = 0.0, notice: float = 0.0) -> "SpotMarket":
        """A one-pool market (``hazard=0`` is the PR-1 degenerate case)."""
        return SpotMarket(pools=(SpotPool(arrival=spot, price=price,
                                          hazard=hazard, notice=notice,
                                          tag=0),))

    def relabel(self, perm: Sequence[int]) -> "SpotMarket":
        """Permute pool positions, keeping each pool's tag (PRNG identity)."""
        if sorted(perm) != list(range(self.n_pools)):
            raise ValueError(f"not a permutation of {self.n_pools} pools")
        return SpotMarket(pools=tuple(self.pools[i] for i in perm))


def as_market(spot) -> SpotMarket:
    """Coerce an :class:`ArrivalProcess` (or a market) to a SpotMarket."""
    if isinstance(spot, SpotMarket):
        return spot
    if isinstance(spot, ArrivalProcess):
        return SpotMarket.single(spot)
    raise TypeError(f"expected ArrivalProcess or SpotMarket, got {spot!r}")


# ---------------------------------------------------------------------------
# The notice law (one source, host + traced — like three_phase_admit_prob)
# ---------------------------------------------------------------------------


def checkpoint_within_notice(checkpoint_time, notice):
    """Can a revoked job checkpoint before its instance disappears?

    Host scalars take the pure-Python path (the cluster orchestrator calls
    this once per live preemption); traced inputs take the jnp path the
    engine kernel scans over.
    """
    if not (isinstance(checkpoint_time, jax.Array)
            or isinstance(notice, jax.Array)):
        return checkpoint_time <= notice
    return jnp.asarray(checkpoint_time, jnp.float32) <= jnp.asarray(
        notice, jnp.float32)


# ---------------------------------------------------------------------------
# Market policy-kernel protocol
# ---------------------------------------------------------------------------


class PoolState(NamedTuple):
    """Non-clairvoyant per-pool state handed to ``admit_market``."""

    price: jax.Array  # (P,) f32  current pool prices c_p
    hazard: jax.Array  # (P,) f32 preemption hazards h_p
    notice: jax.Array  # (P,) f32 notice windows
    rate: jax.Array  # (P,) f32  slot arrival rates (scaled)
    qlen_pool: jax.Array  # (P,) i32 queued jobs per pool


@runtime_checkable
class MarketPolicyKernel(Protocol):
    """Pool-aware policy kernel (superset of the PR-1 two-tuple protocol)."""

    def admit_market(self, params, qlen: jax.Array, pool_state: PoolState,
                     key: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
        """Return (admit?, wait budget, pool index) for an arriving job."""
        ...

    def on_preempt(self, params, age: jax.Array, notice: jax.Array,
                   qlen: jax.Array, key: jax.Array) -> jax.Array:
        """Revoked job: True = checkpoint + re-enter queue, False = defect."""
        ...


def choose_pool(choice: str, pool_state: PoolState, params,
                key: jax.Array) -> jax.Array:
    """Static pool-choice rules shared by the market kernels.

    ``cheapest`` / ``fastest`` / ``least_loaded`` are deterministic argmins;
    ``uniform`` draws uniformly; ``weighted`` Gumbel-samples from traced
    ``params["pool_logits"]`` so the pool distribution itself can be swept
    or learned on-device.
    """
    n = pool_state.price.shape[0]
    if choice == "cheapest":
        return argmin_first(pool_state.price).astype(jnp.int32)
    if choice == "fastest":
        return argmax_first(pool_state.rate).astype(jnp.int32)
    if choice == "least_loaded":
        return argmin_first(pool_state.qlen_pool).astype(jnp.int32)
    if choice == "uniform":
        return jax.random.randint(key, (), 0, n, jnp.int32)
    if choice == "weighted":
        g = jax.random.gumbel(key, (n,), jnp.float32)
        return argmax_first(params["pool_logits"] + g).astype(jnp.int32)
    raise ValueError(f"unknown pool choice rule {choice!r}")


def choose_pool_u(choice: str, pool_state: PoolState, params,
                  u: jax.Array) -> jax.Array:
    """Slab-stream twin of :func:`choose_pool`: draws come from pre-drawn
    float32 uniforms instead of a key (``repro.core.clocks.choice_cols``
    says how many).  Deterministic rules consume nothing; ``uniform`` one
    column; ``weighted`` Gumbel-samples from ``n`` columns.  Equal in
    distribution to the key path, not bitwise.
    """
    n = pool_state.price.shape[0]
    if choice == "uniform":
        return jnp.minimum((u[0] * n).astype(jnp.int32), n - 1)
    if choice == "weighted":
        g = gumbel_from_u(u[:n])
        return argmax_first(params["pool_logits"] + g).astype(jnp.int32)
    return choose_pool(choice, pool_state, params, key=None)


@dataclasses.dataclass(frozen=True)
class PoolChoiceKernel:
    """Adapt any legacy kernel to the market protocol with a choice rule.

    Admission and wait budgets come from ``base.admit``; the pool comes from
    :func:`choose_pool`.  Preempted jobs always defect to on-demand (use
    :class:`NoticeAwareKernel` for checkpoint-aware recovery).
    """

    base: object  # legacy PolicyKernel
    choice: str = "cheapest"

    def admit_market(self, params, qlen, pool_state, key):
        k_adm, k_choice = jax.random.split(key)
        admit, budget = self.base.admit(params, qlen, k_adm)
        return admit, budget, choose_pool(self.choice, pool_state, params,
                                          k_choice)

    def on_preempt(self, params, age, notice, qlen, key):
        del params, age, notice, qlen, key
        return jnp.zeros((), jnp.bool_)

    def slab_cols(self, hook, n):
        if hook == "admit_market":
            base_cols = kernel_slab_cols(self.base, "admit", n)
            if base_cols is None:  # legacy base: whole hook falls back
                return None
            return base_cols + choice_cols(self.choice, n)
        if hook == "on_preempt":
            return 0  # always defects — draws nothing
        return None

    def admit_market_u(self, params, qlen, pool_state, u):
        base_cols = kernel_slab_cols(self.base, "admit",
                                     pool_state.price.shape[0])
        admit, budget = self.base.admit_u(params, qlen, u[:base_cols])
        return admit, budget, choose_pool_u(self.choice, pool_state, params,
                                            u[base_cols:])

    def on_preempt_u(self, params, age, notice, qlen, u):
        del params, age, notice, qlen, u
        return jnp.zeros((), jnp.bool_)


@dataclasses.dataclass(frozen=True)
class NoticeAwareKernel:
    """Three-phase admission + pool choice + checkpoint-within-notice.

    Matches the host orchestrator's preemption model: a revoked job
    checkpoints iff its checkpoint takes no longer than the pool's notice
    window (:func:`checkpoint_within_notice`), then re-enters admission
    under the same Theorem-4 law (``three_phase_admit_prob`` at the current
    queue length) — recovery *is* the admission policy.  Failing either
    test it defects to on-demand.

    Params: ``{"r": f32}`` (+ optional traced ``"ckpt"`` overriding the
    static ``checkpoint_time``, so checkpoint cost can be swept in-jit).
    """

    checkpoint_time: float = 0.05
    choice: str = "cheapest"

    def init_params(self, r: float, ckpt: float | None = None) -> dict:
        p = {"r": jnp.float32(r)}
        if ckpt is not None:
            p["ckpt"] = jnp.float32(ckpt)
        return p

    def admit_market(self, params, qlen, pool_state, key):
        k_adm, k_choice = jax.random.split(key)
        p = three_phase_admit_prob(qlen, params["r"])
        admit = jax.random.uniform(k_adm) < p
        pool = choose_pool(self.choice, pool_state, params, k_choice)
        return admit, _INF, pool

    def on_preempt(self, params, age, notice, qlen, key):
        del age
        ckpt = params.get("ckpt", jnp.float32(self.checkpoint_time))
        within = checkpoint_within_notice(ckpt, notice)
        readmit = jax.random.uniform(key) < three_phase_admit_prob(
            qlen, params["r"])
        return within & readmit

    def slab_cols(self, hook, n):
        if hook == "admit_market":
            return 1 + choice_cols(self.choice, n)  # admission draw + rule
        if hook == "on_preempt":
            return 1  # the re-admission draw
        return None

    def admit_market_u(self, params, qlen, pool_state, u):
        p = three_phase_admit_prob(qlen, params["r"])
        admit = u[0] < p
        pool = choose_pool_u(self.choice, pool_state, params, u[1:])
        return admit, _INF, pool

    def on_preempt_u(self, params, age, notice, qlen, u):
        del age
        ckpt = params.get("ckpt", jnp.float32(self.checkpoint_time))
        within = checkpoint_within_notice(ckpt, notice)
        readmit = u[0] < three_phase_admit_prob(qlen, params["r"])
        return within & readmit


def _failover_alive(target, alive, price):
    """Re-target a dead loc to the cheapest alive one (identity when the
    chosen loc is alive; position 0 when nothing is — callers gate on
    ``jnp.any(alive)``)."""
    cheapest_alive = argmin_first(jnp.where(alive, price, _INF)).astype(
        jnp.int32)
    return jnp.where(alive[target], jnp.asarray(target, jnp.int32),
                     cheapest_alive)


@dataclasses.dataclass(frozen=True)
class PanicKernel:
    """Blackout-failover wrapper: degrade gracefully when supply goes dark.

    A blacked-out pool/region's slot rate is exactly zero (the environment
    timeline multiplies rates by availability before the kernel sees them),
    so ``rate > 0`` is the kernel-visible liveness signal.  PanicKernel
    delegates every decision to ``base`` and then repairs it:

      * an admission targeting a dead pool is re-routed to the cheapest
        alive pool;
      * when EVERY pool is dark the job is rejected outright, falling back
        to on-demand at cost ``k`` — the engine's degraded mode;
      * region routing re-targets dead regions the same way (wrapping a
        routing base repairs its rule; wrapping a non-routing base adds a
        home-unless-dead rule, so any kernel becomes blackout-tolerant).

    The failover consumes no randomness — slab layouts are the base
    kernel's — and with no blackout in the timeline ``alive`` is all-True,
    making every repair the identity: stats are bitwise the base kernel's
    (frozen in tests/test_env.py).

    ``drain_dead=True`` additionally repairs jobs ALREADY QUEUED on a pool
    that goes dark mid-wait: the market event body re-tags every occupied
    slot whose pool has zero availability to the cheapest alive pool
    (the stranded-job caveat — without it those jobs pin ``qlen`` until
    their wait budgets expire).  Opt-in because re-tagging changes which
    slot the next spot arrival serves; identity whenever no blackout is
    active.  Market loop only: the region loop's slot→region map is
    static, so stranded REGION jobs still drain via the deadline path.
    """

    base: object  # any PolicyKernel / MarketPolicyKernel / routing kernel
    drain_dead: bool = False  # re-queue jobs stranded on a dead pool

    # --------------------------------------------------------- admission
    def admit_market(self, params, qlen, pool_state, key):
        if hasattr(self.base, "admit_market"):
            admit, budget, pool = self.base.admit_market(
                params, qlen, pool_state, key)
        else:  # legacy two-tuple kernel: engine would pin it to pool 0
            admit, budget = self.base.admit(params, qlen, key)
            pool = jnp.zeros((), jnp.int32)
        alive = pool_state.rate > 0.0
        pool = _failover_alive(pool, alive, pool_state.price)
        return admit & jnp.any(alive), budget, pool

    def on_preempt(self, params, age, notice, qlen, key):
        if hasattr(self.base, "on_preempt"):
            return self.base.on_preempt(params, age, notice, qlen, key)
        return jnp.zeros((), jnp.bool_)

    # ----------------------------------------------------------- routing
    def route(self, params, qlens, region_state, key):
        if hasattr(self.base, "route"):
            target = self.base.route(params, qlens, region_state, key)
        else:
            target = region_state.home
        alive = region_state.rate > 0.0
        return _failover_alive(target, alive, region_state.price)

    # -------------------------------------------------- slab-stream twins
    def slab_cols(self, hook, n):
        if hook == "route":
            if not hasattr(self.base, "route"):
                return 0  # home fallback draws nothing
            return kernel_slab_cols(self.base, "route", n)
        if hook == "admit_market" and not hasattr(self.base, "admit_market"):
            return kernel_slab_cols(self.base, "admit", n)
        if hook == "on_preempt" and not hasattr(self.base, "on_preempt"):
            return 0  # defect fallback draws nothing
        return kernel_slab_cols(self.base, hook, n)

    def admit_market_u(self, params, qlen, pool_state, u):
        if hasattr(self.base, "admit_market"):
            admit, budget, pool = self.base.admit_market_u(
                params, qlen, pool_state, u)
        else:
            admit, budget = self.base.admit_u(params, qlen, u)
            pool = jnp.zeros((), jnp.int32)
        alive = pool_state.rate > 0.0
        pool = _failover_alive(pool, alive, pool_state.price)
        return admit & jnp.any(alive), budget, pool

    def on_preempt_u(self, params, age, notice, qlen, u):
        if hasattr(self.base, "on_preempt"):
            return self.base.on_preempt_u(params, age, notice, qlen, u)
        return jnp.zeros((), jnp.bool_)

    def route_u(self, params, qlens, region_state, u):
        if hasattr(self.base, "route"):
            target = self.base.route_u(params, qlens, region_state, u)
        else:
            target = region_state.home
        alive = region_state.rate > 0.0
        return _failover_alive(target, alive, region_state.price)

    def __getattr__(self, name):
        # delegate the hooks the wrapper doesn't repair, so the engine's
        # hasattr dispatch sees the base's protocol for them
        if name in ("admit", "admit_u", "init_params"):
            return getattr(object.__getattribute__(self, "base"), name)
        raise AttributeError(name)
