"""Multi-region topology — N queues, per-region clocks, routing at admission.

The paper's model is ONE delay-constrained queue over one spot supply.  Real
fleets span *regions* (cloud region × instance family) with heterogeneous
prices, availability, preemption behaviour — and their own demand: jobs
arrive *in* a region but can be *routed* to any region's queue at admission
(cf. the per-option strategy zoos of Wu et al. and Bhuyan et al. in
PAPERS.md).  This module is the static descriptor layer of the on-device
multi-region subsystem; the event loop lives in :mod:`repro.core.engine`
(``run_region_sim`` / ``run_region_sweep``).

  * :class:`Region` — one region: a job arrival process (demand), a spot
    slot process (supply), price ``c_r``, preemption hazard ``h_r`` +
    notice window (the PR-2 market axes, one pool per region), and a static
    queue capacity ``rmax_r``.
  * :class:`RegionTopology` — a static, hashable tuple of regions.  The
    engine packs the per-region ``(rmax_r,)`` queue partitions as ONE
    ``(sum rmax_r,)`` slot array with a *static* slot→region map, and
    carries per-region ``next_job``/``next_spot``/``next_preempt`` clock
    vectors merged into the renewal loop (ties: spot > preempt > deadline >
    job, regions tie by position — the PR-2 order, unchanged).
  * routing hook — the policy-kernel protocol gains::

        route(params, qlens, region_state, key) -> region

    consulted once per job arrival with the per-region queue lengths and a
    :class:`RegionView` of prices/hazards/rates/occupancy (``home`` is the
    region whose job clock fired).  The admission law then runs against the
    *target* region's queue length, so every existing kernel — three-phase,
    single-slot, NoticeAware — becomes a per-region instance under a
    :class:`RoutingKernel` wrapper.  Kernels without a ``route`` hook keep
    jobs in their home region, which is exactly the degenerate case: a
    1-region topology reproduces the PR-3 engine **bit-for-bit** (frozen in
    tests/test_core_regions.py).
  * Per-region PRNG streams are keyed ``fold_in(key, region.tag)`` — the
    label-independent identity of the PR-2 pools — so permuting regions
    (keeping tags) leaves every sampled stream, and therefore all scalar
    statistics, exactly invariant (property-tested like pool relabeling).

See docs/kernels.md for the full kernel-protocol reference and
EXPERIMENTS.md §"Multi-region" for the modeling rationale and measured
numbers.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arrivals import ArrivalProcess
from repro.core.clocks import (argmax_first, argmin_first, choice_cols,
                               gumbel_from_u)

_INF = np.float32(3e38)  # np scalar: inlines as a literal in kernel traces


# ---------------------------------------------------------------------------
# Descriptors
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Region:
    """One region: demand (job process) + supply (spot process) + economics.

    ``tag`` is the region's stable PRNG-stream identity (defaults to its
    index in the topology); keep tags fixed when permuting regions to get
    bitwise relabel-invariance.  ``rmax`` is the region's static queue
    partition size — regions may be heterogeneous in capacity.
    """

    job: ArrivalProcess
    spot: ArrivalProcess
    price: float = 1.0
    hazard: float = 0.0  # preemption events per unit time on the running job
    notice: float = 0.0  # advance-notice window length
    rmax: int = 64
    tag: int | None = None

    def job_rate(self) -> float:
        return self.job.rate()

    def spot_rate(self) -> float:
        return self.spot.rate()


@dataclasses.dataclass(frozen=True)
class RegionTopology:
    """N heterogeneous regions as one static, hashable descriptor."""

    regions: tuple[Region, ...]

    def __post_init__(self):
        if not self.regions:
            raise ValueError("a RegionTopology needs at least one region")
        tagged = tuple(
            dataclasses.replace(r, tag=i) if r.tag is None else r
            for i, r in enumerate(self.regions)
        )
        tags = [r.tag for r in tagged]
        if len(set(tags)) != len(tags):
            raise ValueError(f"region tags must be unique, got {tags}")
        for r in tagged:
            if r.rmax < 1:
                raise ValueError("every region needs rmax >= 1")
        object.__setattr__(self, "regions", tagged)

    # ------------------------------------------------------------- structure
    @property
    def n_regions(self) -> int:
        return len(self.regions)

    @property
    def total_slots(self) -> int:
        """Size of the packed slot array: sum of per-region ``rmax_r``."""
        return sum(r.rmax for r in self.regions)

    @property
    def preemptible(self) -> bool:
        """Static: does any region carry a preemption hazard?"""
        return any(r.hazard > 0.0 for r in self.regions)

    @property
    def is_degenerate(self) -> bool:
        """1 region, unit price, zero hazard — the PR-3 engine, bit-for-bit."""
        r = self.regions[0]
        return self.n_regions == 1 and r.hazard == 0.0 and r.price == 1.0

    def slot_offsets(self) -> np.ndarray:
        """Static start offset of each region's slot partition (host ints)."""
        return np.cumsum([0] + [r.rmax for r in self.regions[:-1]]).astype(
            np.int32)

    # ------------------------------------------------------------ host views
    def prices(self) -> np.ndarray:
        return np.array([r.price for r in self.regions], np.float64)

    def hazards(self) -> np.ndarray:
        return np.array([r.hazard for r in self.regions], np.float64)

    def notices(self) -> np.ndarray:
        return np.array([r.notice for r in self.regions], np.float64)

    def rates(self) -> np.ndarray:
        """Per-region spot slot rates μ_r (the supply side; the name matches
        :meth:`repro.core.market.SpotMarket.rates` so the topology plugs
        straight into :func:`repro.core.lp.market_knapsack_lp`)."""
        return np.array([r.spot_rate() for r in self.regions], np.float64)

    def job_rates(self) -> np.ndarray:
        return np.array([r.job_rate() for r in self.regions], np.float64)

    def total_job_rate(self) -> float:
        return float(self.job_rates().sum())

    def rmaxes(self) -> np.ndarray:
        return np.array([r.rmax for r in self.regions], np.int32)

    # --------------------------------------------------------- traced params
    def params(self) -> dict:
        """Traced region-config pytree consumed by the engine event loop.

        ``spot_scale``/``job_scale`` multiply inter-arrival times (scale > 1
        = scarcer slots / slower demand) — distribution-generic availability
        and demand axes a sweep can trace without retracing the arrival
        families.  ``rate``/``job_rate`` ride in the traced params (not
        materialized in the event body) so the body stays
        constant-capture-free under the Pallas kernel trace; ``rmax`` rides
        along for the same reason (the capacity check needs the per-region
        vector, and an inline jnp constant would be hoisted as a const,
        which pallas_call rejects).
        """
        n = self.n_regions
        return {
            "price": jnp.asarray(self.prices(), jnp.float32),
            "hazard": jnp.asarray(self.hazards(), jnp.float32),
            "notice": jnp.asarray(self.notices(), jnp.float32),
            "spot_scale": jnp.ones((n,), jnp.float32),
            "job_scale": jnp.ones((n,), jnp.float32),
            "rate": jnp.asarray(self.rates(), jnp.float32),
            "job_rate": jnp.asarray(self.job_rates(), jnp.float32),
            "rmax": jnp.asarray(self.rmaxes(), jnp.int32),
        }

    # ------------------------------------------------------------- utilities
    @staticmethod
    def single(job: ArrivalProcess, spot: ArrivalProcess, *,
               price: float = 1.0, hazard: float = 0.0, notice: float = 0.0,
               rmax: int = 64) -> "RegionTopology":
        """A one-region topology (``hazard=0, price=1`` is the PR-3
        degenerate case)."""
        return RegionTopology(regions=(Region(
            job=job, spot=spot, price=price, hazard=hazard, notice=notice,
            rmax=rmax, tag=0),))

    def relabel(self, perm: Sequence[int]) -> "RegionTopology":
        """Permute region positions, keeping each region's tag (PRNG
        identity)."""
        if sorted(perm) != list(range(self.n_regions)):
            raise ValueError(f"not a permutation of {self.n_regions} regions")
        return RegionTopology(regions=tuple(self.regions[i] for i in perm))


def as_topology(obj) -> RegionTopology:
    """Coerce a Region (or a topology) to a RegionTopology."""
    if isinstance(obj, RegionTopology):
        return obj
    if isinstance(obj, Region):
        return RegionTopology(regions=(obj,))
    raise TypeError(f"expected Region or RegionTopology, got {obj!r}")


# ---------------------------------------------------------------------------
# Routing-kernel protocol
# ---------------------------------------------------------------------------


class RegionView(NamedTuple):
    """Non-clairvoyant per-region state handed to the ``route`` hook.

    ``home`` is the region whose job clock fired (where the job physically
    arrived); routing elsewhere models cross-region dispatch.  All vectors
    are indexed by region *position* (permute with the topology).
    """

    home: jax.Array  # () i32   arrival region of the current job
    price: jax.Array  # (R,) f32 region prices c_r
    hazard: jax.Array  # (R,) f32 preemption hazards h_r
    notice: jax.Array  # (R,) f32 notice windows
    rate: jax.Array  # (R,) f32  spot slot rates (scaled)
    job_rate: jax.Array  # (R,) f32 job arrival rates (scaled)
    qlen_region: jax.Array  # (R,) i32 queued jobs per region
    free_slots: jax.Array  # (R,) i32 remaining capacity rmax_r − qlen_r


def choose_region(choice: str, view: RegionView, params,
                  key: jax.Array) -> jax.Array:
    """Static routing rules shared by :class:`RoutingKernel` instances.

    ``home`` keeps the job where it arrived; ``cheapest`` / ``fastest`` /
    ``least_loaded`` are deterministic argmins over the region vectors
    (label-independent when the decided-on values are distinct); ``uniform``
    draws uniformly; ``weighted`` Gumbel-samples from traced
    ``params["region_logits"]`` so the routing distribution itself can be
    swept or learned on-device — the exact shape of
    :func:`repro.core.market.choose_pool`, one level up.
    """
    n = view.price.shape[0]
    if choice == "home":
        return view.home
    if choice == "cheapest":
        return argmin_first(view.price).astype(jnp.int32)
    if choice == "fastest":
        return argmax_first(view.rate).astype(jnp.int32)
    if choice == "least_loaded":
        return argmin_first(view.qlen_region).astype(jnp.int32)
    if choice == "uniform":
        return jax.random.randint(key, (), 0, n, jnp.int32)
    if choice == "weighted":
        g = jax.random.gumbel(key, (n,), jnp.float32)
        return argmax_first(params["region_logits"] + g).astype(jnp.int32)
    raise ValueError(f"unknown routing rule {choice!r}")


def choose_region_u(choice: str, view: RegionView, params,
                    u: jax.Array) -> jax.Array:
    """Slab-stream twin of :func:`choose_region` (pre-drawn uniforms
    instead of a key; ``repro.core.clocks.choice_cols`` widths) — the
    routing analogue of :func:`repro.core.market.choose_pool_u`."""
    n = view.price.shape[0]
    if choice == "uniform":
        return jnp.minimum((u[0] * n).astype(jnp.int32), n - 1)
    if choice == "weighted":
        g = gumbel_from_u(u[:n])
        return argmax_first(params["region_logits"] + g).astype(jnp.int32)
    return choose_region(choice, view, params, key=None)


def host_route(choice: str, *, prices, rates, qlens, home: int = 0,
               alive=None) -> int:
    """Host-scalar twin of the deterministic :func:`choose_region` rules.

    The cluster orchestrator routes one live job at a time; an un-jitted
    jnp round-trip costs ~1 ms per call (same dual-backend reasoning as
    ``three_phase_admit_prob``).  Randomized rules (uniform/weighted) stay
    on the traced path — the host consumer passes its own rng draw instead.

    ``alive`` (optional bool mask) restricts every rule to live regions —
    the host twin of :class:`repro.core.market.PanicKernel`'s failover: a
    dead ``home`` falls back to the cheapest alive region, and argmin/argmax
    rules never pick a dead one.  All-dead raises ``RuntimeError`` (the
    orchestrator's cue to run the job on-demand).
    """
    prices = np.asarray(prices, np.float64)
    rates = np.asarray(rates, np.float64)
    qlens = np.asarray(qlens, np.float64)
    if alive is not None:
        alive = np.asarray(alive, bool)
        if not alive.any():
            raise RuntimeError("host_route: no region alive")
        dead = ~alive
        if choice == "home" and dead[int(home)]:
            choice = "cheapest"  # failover: home is dark
        prices = np.where(dead, np.inf, prices)
        rates = np.where(dead, -np.inf, rates)
        qlens = np.where(dead, np.inf, qlens)
    if choice == "home":
        return int(home)
    if choice == "cheapest":
        return int(np.argmin(prices))
    if choice == "fastest":
        return int(np.argmax(rates))
    if choice == "least_loaded":
        return int(np.argmin(qlens))
    raise ValueError(f"unknown host routing rule {choice!r}")


@dataclasses.dataclass(frozen=True)
class RoutingKernel:
    """Adapt any engine kernel to the multi-region protocol with a rule.

    Admission (and wait budgets, and market/preemption hooks if the base
    has them) delegate to ``base``, evaluated against the *target* region's
    queue length; the target comes from :func:`choose_region`.  Mirrors
    PR-2's :class:`repro.core.market.PoolChoiceKernel`, one level up: wrap
    ``ThreePhaseKernel`` / ``SingleSlotKernel`` / ``NoticeAwareKernel`` and
    each region runs its own per-region instance of the paper's policy.

    Note on blackouts: the region loop's slot→region map is STATIC, so
    jobs already queued in a region that goes dark cannot be re-tagged
    (the market loop's ``PanicKernel(drain_dead=True)`` repair has no
    region analogue) — stranded region jobs drain through their wait
    budgets / the deadline path.  Routing only protects NEW admissions.
    """

    base: object  # any PolicyKernel / MarketPolicyKernel
    choice: str = "cheapest"

    def route(self, params, qlens, region_state: RegionView, key):
        del qlens  # already carried by region_state.qlen_region
        return choose_region(self.choice, region_state, params, key)

    def slab_cols(self, hook, n):
        if hook == "route":
            return choice_cols(self.choice, n)
        base_cols = getattr(object.__getattribute__(self, "base"),
                            "slab_cols", None)
        return base_cols(hook, n) if base_cols is not None else None

    def route_u(self, params, qlens, region_state: RegionView, u):
        del qlens
        return choose_region_u(self.choice, region_state, params, u)

    def __getattr__(self, name):
        # delegate the admission/preemption hooks the base actually has, so
        # the engine's hasattr dispatch sees exactly the base's protocol
        # (key-based hooks and their slab-stream ``*_u`` twins alike)
        if name in ("admit", "admit_market", "on_preempt", "init_params",
                    "admit_u", "admit_market_u", "on_preempt_u"):
            return getattr(object.__getattribute__(self, "base"), name)
        raise AttributeError(name)
