"""Spot/on-demand cluster orchestration driven by the paper's policy.

This is the paper *deployed*: a stream of delay-sensitive jobs (training
legs / batch-inference requests) arrives at a cluster whose cheap capacity
is spot pods (stochastic availability, advance-notice preemption) and whose
guaranteed capacity is on-demand pods at cost ``k``.

Since PR 2 the host path is a **thin consumer of the on-device spot-market
subsystem** (:mod:`repro.core.market`): the cluster's capacity model is a
:class:`~repro.core.market.SpotMarket` — P heterogeneous pools with
per-pool prices, slot processes, and Poisson preemption hazards — and the
live event loop mirrors the engine's merged clock vector (per-pool
``next_slot``/``next_preempt`` + the job clock).  Every law is shared with
the traced kernels: admission goes through
:func:`repro.core.policies.three_phase_admit_prob`, preemption recovery
through :func:`repro.core.market.checkpoint_within_notice` + re-admission
(exactly :class:`repro.core.market.NoticeAwareKernel`), and
:meth:`SpotCluster.what_if_sweep` hands the live controller state to
:func:`repro.core.engine.run_market_sweep` for on-device what-if grids
against the *same* market the host is serving.

Components:
  * :class:`OnlineAdmissionController` — Algorithm 1 running *online* on the
    live event stream (the jit'd scan in repro.core.adaptive is the
    offline/on-device twin; this one consumes real callbacks), plus the
    pool-choice hook (cheapest-price, the engine kernels' default rule).
  * :class:`SpotCluster` — discrete-event cluster: job arrivals, per-pool
    spot slots, hazard-clock preemptions with notice, and the legacy
    Bernoulli preemption-at-service model (``preemption_prob``).  Jobs
    admitted to the spot queue are tagged with a pool and wait (Theorem 4:
    X = ∞ below the knob); rejected jobs run on-demand immediately.
    Preempted jobs checkpoint within the notice window and re-enter
    admission — the paper's policy doubles as the recovery policy.
  * Straggler mitigation: per-pod EWMA of step time; a pod flagged at
    >``straggler_factor``× the median is treated as preempted-with-notice.

The event loop is host-side Python (it orchestrates real JAX work — see
examples/elastic_spot_training.py); all statistics mirror the engine's
market accounting so Theorem-1 cost laws apply unchanged.
"""
from __future__ import annotations

import dataclasses
import math
from collections import deque
from typing import Callable, Optional

import numpy as np

from repro.core.arrivals import ArrivalProcess
from repro.core.clocks import hazard_clock, thinning_pick
from repro.core.market import (
    NoticeAwareKernel,
    SpotMarket,
    checkpoint_within_notice,
)
from repro.core.policies import (
    ThreePhaseKernel,
    ThreePhasePolicy,
    deadline_slack,
    three_phase_admit_prob,
)
from repro.core.regions import RegionTopology, host_route
from repro.obs.timing import EntrySpan
from repro.obs.trace import TraceRecorder


class OnlineAdmissionController:
    """Algorithm 1 on a live stream: windowed delay → projected SGD on r."""

    def __init__(self, *, delta: float, eta: float = 0.05,
                 eta_decay: float = 0.05, r0: float = 1.0,
                 r_max: float = 16.0, window_jobs: int = 64):
        self.delta = delta
        self.eta = eta
        self.eta_decay = eta_decay
        self.r = r0
        self.r_max = r_max
        self.window_jobs = window_jobs
        self._delays: list[float] = []
        self._updates = 0
        self.history: list[float] = [r0]

    def policy(self) -> ThreePhasePolicy:
        return ThreePhasePolicy(r=self.r)

    def kernel(self) -> ThreePhaseKernel:
        """The engine kernel twin; pair with :meth:`kernel_params`."""
        return ThreePhaseKernel()

    def kernel_params(self) -> dict:
        return self.policy().kernel_params()

    def admit(self, queue_len: int, rng: np.random.Generator) -> bool:
        return rng.random() < three_phase_admit_prob(queue_len, self.r)

    def choose_pool(self, market: SpotMarket, qlen_pool: list[int],
                    alive=None) -> int:
        """Pool-choice hook — cheapest price, the engine kernels' default.

        ``alive`` (optional bool mask) restricts the choice to live pools
        — the host twin of :class:`repro.core.market.PanicKernel`; all-dead
        raises ``RuntimeError`` (the cluster's cue to run on-demand).
        """
        del qlen_pool
        prices = market.prices()
        if alive is not None:
            alive = np.asarray(alive, bool)
            if not alive.any():
                raise RuntimeError("choose_pool: no pool alive")
            prices = np.where(alive, prices, np.inf)
        return int(np.argmin(prices))

    def choose_region(self, topology: RegionTopology,
                      qlen_region: list[int], home: int = 0,
                      rule: str = "cheapest", alive=None) -> int:
        """Routing hook — the deterministic :func:`repro.core.regions.
        host_route` rules (host twin of the engine's ``route`` hook).
        ``alive`` forwards the region health mask (failover semantics in
        :func:`repro.core.regions.host_route`)."""
        return host_route(rule, prices=topology.prices(),
                          rates=topology.rates(), qlens=qlen_region,
                          home=home, alive=alive)

    def on_job_complete(self, delay: float) -> None:
        self._delays.append(delay)
        if len(self._delays) >= self.window_jobs:
            d = float(np.mean(self._delays))
            self._delays.clear()
            step = self.eta / math.sqrt(1.0 + self.eta_decay * self._updates)
            self._updates += 1
            self.r = min(self.r_max, max(0.0, self.r - step * (d - self.delta)))
            self.history.append(self.r)


def _sample_interarrival(proc: ArrivalProcess,
                         rng: np.random.Generator) -> float:
    """One inter-arrival draw for a host clock (shared by both clusters)."""
    import jax

    key = jax.random.key(int(rng.integers(2**31)))
    return float(proc.sample(key))


def _sample_superposed_preempt(hazards,
                               rng: np.random.Generator) -> tuple[float, int]:
    """(time, pool) of the next preemption under the superposed clock.

    Host twin of the engine's ``rng="slab"`` preemption machinery: ONE
    ``Exp(Σ h_p)`` draw plus a hazard-weighted thinning pick replaces the
    per-pool clock vector — the same shared law
    (:func:`repro.core.clocks.hazard_clock` /
    :func:`repro.core.clocks.thinning_pick`), exactly the vector clocks'
    joint (min, argmin) distribution.
    """
    return (hazard_clock(hazards, rng.random()),
            thinning_pick(hazards, rng.random()))


@dataclasses.dataclass(frozen=True)
class ExponentialBackoff:
    """Retry schedule for re-admission after a preemption under supply
    stress: a revoked job whose first re-admission draw fails waits
    ``base_delay``, retries, and doubles the wait up to ``max_retries``
    times before defecting to on-demand.  Host-side resilience knob —
    the clusters take ``retry=ExponentialBackoff(...)``; the default
    (``retry=None``) draws nothing and reproduces the historical event
    stream bit-for-bit.
    """

    base_delay: float = 0.05
    factor: float = 2.0
    max_retries: int = 3

    def __post_init__(self):
        if self.base_delay <= 0 or self.factor < 1 or self.max_retries < 1:
            raise ValueError("backoff needs base_delay>0, factor>=1, "
                             "max_retries>=1")

    def delays(self):
        d = self.base_delay
        for _ in range(self.max_retries):
            yield d
            d *= self.factor


def _retry_admit(ctl, rng, retry: ExponentialBackoff, qlen: int,
                 stats) -> tuple[bool, float]:
    """Backed-off re-admission attempts: (admitted?, extra wait charged).

    Shared by both clusters' preemption recovery: each attempt waits the
    next backoff delay (charged to the job either way) and redraws the
    admission law; exhaustion defects to on-demand.
    """
    extra = 0.0
    for wait in retry.delays():
        stats.retries += 1
        extra += wait
        if ctl.admit(qlen, rng):
            return True, extra
    return False, extra


@dataclasses.dataclass
class Job:
    job_id: int
    arrival_time: float
    work_steps: int  # training steps this job needs
    pool: int = 0  # spot pool the job is placed on


@dataclasses.dataclass
class ClusterStats:
    jobs_completed: int = 0
    spot_served: int = 0
    ondemand_served: int = 0
    preemptions: int = 0
    stragglers_evicted: int = 0
    checkpoints: int = 0
    restores: int = 0
    total_cost: float = 0.0
    total_delay: float = 0.0
    spot_cost: float = 0.0  # spend on spot pools incl. partial legs
    retries: int = 0  # backed-off re-admission attempts (retry= set)
    degraded_jobs: int = 0  # forced on-demand: no live pool/region

    @property
    def avg_cost(self) -> float:
        return self.total_cost / max(self.jobs_completed, 1)

    @property
    def avg_delay(self) -> float:
        return self.total_delay / max(self.jobs_completed, 1)


class SpotCluster:
    """Discrete-event spot/on-demand cluster with admission control.

    Capacity is described by a :class:`SpotMarket`; the classic single-pool
    constructor (``spot_process=...``) builds the degenerate one-pool market
    and behaves exactly as before.  Pool preemption hazards fire host-side
    clocks that mirror the engine's ``next_preempt`` vector; the legacy
    ``preemption_prob`` Bernoulli-at-service model is kept for callers that
    want revocation without hazard clocks.
    """

    def __init__(self, *, job_process: ArrivalProcess,
                 spot_process: Optional[ArrivalProcess] = None,
                 market: Optional[SpotMarket] = None, k_cost: float = 10.0,
                 controller: OnlineAdmissionController,
                 preemption_prob: float = 0.0,
                 notice_hours: float = 0.05,
                 checkpoint_hours: float = 0.0,
                 straggler_factor: float = 1.5,
                 on_spot_run: Optional[Callable] = None,
                 on_ondemand_run: Optional[Callable] = None,
                 on_preempt: Optional[Callable] = None,
                 tracer: Optional[TraceRecorder] = None,
                 retry: Optional[ExponentialBackoff] = None,
                 seed: int = 0):
        if (market is None) == (spot_process is None):
            raise ValueError("pass exactly one of spot_process / market")
        if market is None:
            market = SpotMarket.single(spot_process, notice=notice_hours)
        self.market = market
        self.jobs = job_process
        self.k = k_cost
        self.ctl = controller
        self.preemption_prob = preemption_prob
        self.notice = notice_hours
        self.checkpoint_hours = checkpoint_hours
        self.straggler_factor = straggler_factor
        self.on_spot_run = on_spot_run
        self.on_ondemand_run = on_ondemand_run
        self.on_preempt = on_preempt
        self.tracer = tracer
        self.retry = retry
        self.rng = np.random.default_rng(seed)
        self.queue: deque[Job] = deque()
        self.stats = ClusterStats()
        self.pool_served = [0] * market.n_pools
        self.pool_alive = [True] * market.n_pools
        self._t = 0.0
        self._job_counter = 0
        self._step_times: dict[int, float] = {}  # pod EWMA

    # --------------------------------------------------------------- health
    def kill_pool(self, pool: int) -> None:
        """Mark a pool dark (blackout): its slots stop serving and new
        admissions route around it.  Queued jobs wait for :meth:`revive_pool`
        (paused instances), exactly the engine's blackout semantics."""
        self.pool_alive[pool] = False

    def revive_pool(self, pool: int) -> None:
        self.pool_alive[pool] = True

    # --------------------------------------------------------------- events
    def _sample(self, proc: ArrivalProcess) -> float:
        return _sample_interarrival(proc, self.rng)

    def run(self, n_events: int, *, work_steps: int = 1) -> ClusterStats:
        """Run the merged per-pool clock loop (job-first on exact ties,
        the host's historical order; ties are measure-zero for continuous
        samplers)."""
        pools = self.market.pools
        hazards = self.market.hazards()
        next_job = self._sample(self.jobs)
        next_slot = [self._sample(p.arrival) for p in pools]
        # ONE superposed preemption clock for the whole market (the shared
        # hazard-superposition law; see _sample_superposed_preempt)
        next_pre, p_pre = _sample_superposed_preempt(hazards, self.rng)
        for _ in range(n_events):
            p_slot = int(np.argmin(next_slot))
            m_slot = next_slot[p_slot]
            dt = min(next_job, m_slot, next_pre)
            self._t += dt
            next_job -= dt
            for p in range(len(pools)):
                next_slot[p] -= dt
            if math.isfinite(next_pre):
                next_pre -= dt
            if next_job <= 0.0:
                next_job = self._sample(self.jobs)
                self._job_arrival(work_steps)
            elif next_slot[p_slot] <= 0.0:
                next_slot[p_slot] = self._sample(pools[p_slot].arrival)
                self._spot_arrival(p_slot)
            else:
                fired = p_pre
                next_pre, p_pre = _sample_superposed_preempt(hazards,
                                                             self.rng)
                self._preempt_event(fired)
        return self.stats

    def _qlen_pool(self) -> list[int]:
        counts = [0] * self.market.n_pools
        for job in self.queue:
            counts[job.pool] += 1
        return counts

    def _job_arrival(self, work_steps: int) -> None:
        self._job_counter += 1
        if all(self.pool_alive):  # healthy path: the historical call shape
            pool = self.ctl.choose_pool(self.market, self._qlen_pool())
        else:
            try:
                pool = self.ctl.choose_pool(self.market, self._qlen_pool(),
                                            alive=self.pool_alive)
            except RuntimeError:  # every pool dark: degrade to on-demand
                self.stats.degraded_jobs += 1
                self._run_ondemand(Job(self._job_counter, self._t,
                                       work_steps))
                return
        job = Job(self._job_counter, self._t, work_steps, pool=pool)
        if self.ctl.admit(len(self.queue), self.rng):
            self.queue.append(job)  # Theorem 4: wait indefinitely
        else:
            self._run_ondemand(job)
        if self.tracer is not None:
            self.tracer.record(self._t, "job", loc=pool,
                               qlen=len(self.queue))

    def _pop_oldest(self, pool: int) -> Optional[Job]:
        for i, job in enumerate(self.queue):  # FIFO-oldest on this pool
            if job.pool == pool:
                del self.queue[i]
                return job
        return None

    def _spot_arrival(self, pool_idx: int) -> None:
        if not self.pool_alive[pool_idx]:
            return  # dark pool: the slot never materializes
        job = self._pop_oldest(pool_idx)
        if self.tracer is not None:
            self.tracer.record(
                self._t, "spot", loc=pool_idx, qlen=len(self.queue),
                **({} if job is None
                   else {"wait": self._t - job.arrival_time}))
        if job is None:
            return
        price = self.market.pools[pool_idx].price
        delay = self._t - job.arrival_time
        preempted = self.rng.random() < self.preemption_prob
        if preempted:
            # legacy Bernoulli-at-service revocation: checkpoint within the
            # notice -> re-admission (recovery = policy).  The same notice
            # law as the hazard-clock path gates the checkpoint; the
            # default checkpoint_hours=0.0 always fits (historical
            # behaviour).
            self.stats.preemptions += 1
            if self.on_preempt is not None:
                self.on_preempt(job)
            self.stats.total_cost += price  # the partial spot leg was paid
            self.stats.spot_cost += price
            pool = self.market.pools[pool_idx]
            within = checkpoint_within_notice(self.checkpoint_hours,
                                              pool.notice)
            if within:
                self.stats.checkpoints += 1
            if within and self.ctl.admit(len(self.queue), self.rng):
                self.stats.restores += 1
                self.queue.append(dataclasses.replace(
                    job, arrival_time=self._t))
                self.stats.total_delay += delay
                # completion will be counted when the retry finishes
                self.ctl.on_job_complete(delay)
                self.stats.jobs_completed += 1  # leg accounting
            else:
                self._run_ondemand(job, extra_delay=delay)
            return
        if self.on_spot_run is not None:
            self.on_spot_run(job)
        self.stats.jobs_completed += 1
        self.stats.spot_served += 1
        self.pool_served[pool_idx] += 1
        self.stats.total_cost += price
        self.stats.spot_cost += price
        self.stats.total_delay += delay
        self.ctl.on_job_complete(delay)

    def _preempt_event(self, pool_idx: int) -> None:
        """Hazard-clock revocation: the engine's preempt event, host-side.

        The FIFO-oldest pool job loses its instance; the partial leg is
        paid; the job checkpoints iff it fits the notice window
        (:func:`checkpoint_within_notice`) AND re-admission accepts it —
        else it defects to on-demand.  Mirrors NoticeAwareKernel exactly.
        """
        job = self._pop_oldest(pool_idx)
        if self.tracer is not None:
            self.tracer.record(self._t, "preempt", loc=pool_idx,
                               qlen=len(self.queue))
        if job is None:
            return  # the revoked instance was idle
        pool = self.market.pools[pool_idx]
        delay = self._t - job.arrival_time
        self.stats.preemptions += 1
        if self.on_preempt is not None:
            self.on_preempt(job)
        self.stats.total_cost += pool.price
        self.stats.spot_cost += pool.price
        within = checkpoint_within_notice(self.checkpoint_hours, pool.notice)
        if within:
            self.stats.checkpoints += 1
        admitted = within and self.ctl.admit(len(self.queue), self.rng)
        extra = 0.0
        if within and not admitted and self.retry is not None:
            admitted, extra = _retry_admit(self.ctl, self.rng, self.retry,
                                           len(self.queue), self.stats)
        if admitted:
            self.stats.restores += 1
            self.queue.append(dataclasses.replace(job, arrival_time=self._t))
            self.stats.total_delay += delay + extra
            self.stats.jobs_completed += 1  # leg accounting
            self.ctl.on_job_complete(delay + extra)
        else:
            self._run_ondemand(job, extra_delay=delay + extra)

    def _run_ondemand(self, job: Job, extra_delay: float = 0.0) -> None:
        if self.on_ondemand_run is not None:
            self.on_ondemand_run(job)
        self.stats.jobs_completed += 1
        self.stats.ondemand_served += 1
        self.stats.total_cost += self.k
        self.stats.total_delay += extra_delay
        self.ctl.on_job_complete(extra_delay)

    # ---------------------------------------------------- on-device what-if
    def what_if_sweep(self, rs, *, n_events: int = 20_000, n_seeds: int = 2,
                      k=None, key=None, telemetry=None, shard: str = "none",
                      mesh=None) -> dict:
        """Sweep admission knobs against THIS cluster's market, on-device.

        Runs :func:`repro.core.engine.run_market_sweep` with the cluster's
        market and recovery parameters — the host is a thin consumer: the
        what-if grid for "where should the controller's r sit" is one
        compiled program, not a host loop.  ``telemetry=`` forwards a
        :class:`repro.obs.Telemetry` so the grid also reports P50/P99
        waits and per-pool counters.  ``shard="lanes"`` (with an optional
        ``mesh``) partitions the what-if lane axis across devices exactly
        as in :func:`repro.core.engine.run_sweep` — wide grids answer at
        fleet scale (docs/scaling.md).

        The executor follows the backend (:func:`_what_if_executor`): the
        compiled batched-event kernel on a TPU, the XLA scan elsewhere,
        both on the ``rng="slab"`` stream, so integer stats agree bitwise
        across backends.  Its answers differ key for key from those of the
        ``rng="split"`` stream it ran before, under the same laws; that
        stream stays one :func:`~repro.core.engine.run_market_sweep` call
        away.
        """
        import jax

        from repro.core.engine import run_market_sweep

        with EntrySpan("repro.cluster.what_if_sweep[market]"):
            if key is None:
                key = jax.random.key(int(self.rng.integers(2**31)))
            kern = NoticeAwareKernel(checkpoint_time=self.checkpoint_hours)
            # the engine joins this call's span and phases, and casts ``rs``
            # inside its one jitted call (a list becomes one host array
            # here, not a pytree of scalars)
            if not isinstance(rs, jax.Array):
                rs = np.asarray(rs)
            return run_market_sweep(
                self.jobs, self.market, kern, {"r": rs},
                k=self.k if k is None else k, n_events=n_events, key=key,
                n_seeds=n_seeds, telemetry=telemetry, shard=shard, mesh=mesh,
                **_what_if_executor(),
            )

    # ------------------------------------------------------ deadline slack
    def job_slack(self, *, deadline: float, job: Job,
                  od_step_hours: float, buffer: float = 0.0) -> float:
        """Host-side can't-be-late watchdog for a live job.

        The engine's :class:`~repro.core.work.CantBeLateKernel` law on the
        orchestrator's clock: how much longer ``job`` may keep waiting on
        spot before migrating to on-demand (``od_step_hours`` per
        remaining work step) would no longer meet ``deadline``
        (:func:`repro.core.policies.deadline_slack` — the same arithmetic
        the traced watchdog uses).  ``<= 0`` means migrate NOW.
        """
        return float(deadline_slack(deadline, self._t - job.arrival_time,
                                    float(job.work_steps), od_step_hours,
                                    buffer))

    # ----------------------------------------------------------- stragglers
    def observe_step_time(self, pod_id: int, seconds: float) -> bool:
        """EWMA straggler detector; returns True if the pod was evicted."""
        prev = self._step_times.get(pod_id, seconds)
        ewma = 0.7 * prev + 0.3 * seconds
        self._step_times[pod_id] = ewma
        if len(self._step_times) >= 2:
            median = float(np.median(list(self._step_times.values())))
            if ewma > self.straggler_factor * median:
                self.stats.stragglers_evicted += 1
                del self._step_times[pod_id]
                return True
        return False


def _what_if_executor() -> dict:
    """:meth:`SpotCluster.what_if_sweep`'s executor, from the backend:
    Mosaic compiles the kernel for every what-if variant (``telemetry=``
    and ``shard="lanes"`` too; tests/test_tpu_compile.py), and the kernel
    runs the slab stream only."""
    from repro.kernels.sweep.ops import default_interpret

    if default_interpret():
        return {"impl": "xla", "rng": "slab"}
    return {"impl": "pallas", "interpret": False, "rng": "slab"}


@dataclasses.dataclass
class RegionClusterStats(ClusterStats):
    """Cluster stats + per-region served/routed counters.

    :class:`MultiRegionCluster` constructs the per-region lists at
    topology size; a bare ``RegionClusterStats()`` starts them empty.
    """

    region_served: list = dataclasses.field(default_factory=list)
    region_routed: list = dataclasses.field(default_factory=list)
    cross_region: int = 0


class MultiRegionCluster:
    """Host-side multi-region routing over a :class:`RegionTopology`.

    The live twin of the engine's region loop (``run_region_sim``): one
    merged host clock set — per-region job arrivals, spot slots, and hazard
    preemptions — with routing at admission through the controller's
    :meth:`OnlineAdmissionController.choose_region` hook and admission
    against the *target* region's queue (per-region instances of the
    three-phase law, exactly the traced :class:`repro.core.regions.
    RoutingKernel` semantics).  Preempted jobs follow the PR-2 recovery
    model: pay the partial leg, checkpoint within the notice window
    (:func:`repro.core.market.checkpoint_within_notice`), re-enter
    admission in their own region.  Statistics mirror the engine's region
    accounting so the Theorem-1 region cost law applies unchanged;
    :meth:`what_if_sweep` hands the live topology to
    :func:`repro.core.engine.run_region_sweep` for on-device what-if grids.
    """

    #: routing rules the live host loop supports — the deterministic
    #: subset of :func:`repro.core.regions.choose_region` (randomized
    #: rules stay on the traced path; ``what_if_sweep`` accepts them all
    #: via ``choice=``)
    HOST_ROUTES = ("home", "cheapest", "fastest", "least_loaded")

    def __init__(self, *, topology: RegionTopology,
                 controller: OnlineAdmissionController,
                 k_cost: float = 10.0, route: str = "cheapest",
                 checkpoint_hours: float = 0.0,
                 tracer: Optional[TraceRecorder] = None,
                 retry: Optional[ExponentialBackoff] = None, seed: int = 0):
        if route not in self.HOST_ROUTES:
            raise ValueError(
                f"unknown host routing rule {route!r}; the live loop "
                f"supports {self.HOST_ROUTES} (randomized rules run "
                f"on-device — pass them to what_if_sweep(choice=...))")
        self.topology = topology
        self.ctl = controller
        self.k = k_cost
        self.route = route
        self.checkpoint_hours = checkpoint_hours
        self.tracer = tracer
        self.retry = retry
        self.rng = np.random.default_rng(seed)
        self.queues: list[deque[Job]] = [deque()
                                         for _ in topology.regions]
        self.stats = RegionClusterStats(
            region_served=[0] * topology.n_regions,
            region_routed=[0] * topology.n_regions)
        self.region_alive = [True] * topology.n_regions
        self._t = 0.0
        self._job_counter = 0

    # --------------------------------------------------------------- health
    def kill_region(self, region: int, *, drain: bool = False) -> None:
        """Mark a region dark (blackout): its slots stop serving and new
        admissions route around it (:func:`repro.core.regions.host_route`
        with the alive mask).  Queued jobs wait for :meth:`revive_region`
        (paused instances — the engine's blackout semantics); with
        ``drain=True`` they defect to on-demand immediately instead.
        """
        self.region_alive[region] = False
        if drain:
            queue = self.queues[region]
            while queue:
                job = queue.popleft()
                self.stats.degraded_jobs += 1
                self._run_ondemand(job,
                                   extra_delay=self._t - job.arrival_time)

    def revive_region(self, region: int) -> None:
        self.region_alive[region] = True

    # --------------------------------------------------------------- events
    def _sample(self, proc: ArrivalProcess) -> float:
        return _sample_interarrival(proc, self.rng)

    def qlen_region(self) -> list[int]:
        return [len(q) for q in self.queues]

    def run(self, n_events: int) -> RegionClusterStats:
        """Run the merged per-region clock loop (tie order: slot > preempt
        > job, regions tie by position — ties are measure-zero for
        continuous samplers)."""
        regions = self.topology.regions
        hazards = self.topology.hazards()
        next_job = [self._sample(r.job) for r in regions]
        next_slot = [self._sample(r.spot) for r in regions]
        # ONE superposed preemption clock across regions (shared law; see
        # _sample_superposed_preempt)
        next_pre, r_pre = _sample_superposed_preempt(hazards, self.rng)
        for _ in range(n_events):
            r_job = int(np.argmin(next_job))
            r_slot = int(np.argmin(next_slot))
            dt = min(next_job[r_job], next_slot[r_slot], next_pre)
            self._t += dt
            for r in range(len(regions)):
                next_job[r] -= dt
                next_slot[r] -= dt
            if math.isfinite(next_pre):
                next_pre -= dt
            if next_slot[r_slot] <= 0.0:
                next_slot[r_slot] = self._sample(regions[r_slot].spot)
                self._spot_arrival(r_slot)
            elif next_pre <= 0.0:
                fired = r_pre
                next_pre, r_pre = _sample_superposed_preempt(hazards,
                                                             self.rng)
                self._preempt_event(fired)
            else:
                next_job[r_job] = self._sample(regions[r_job].job)
                self._job_arrival(r_job)
        return self.stats

    def _job_arrival(self, home: int) -> None:
        self._job_counter += 1
        if all(self.region_alive):  # healthy path: historical call shape
            target = self.ctl.choose_region(self.topology,
                                            self.qlen_region(), home=home,
                                            rule=self.route)
        else:
            try:
                target = self.ctl.choose_region(
                    self.topology, self.qlen_region(), home=home,
                    rule=self.route, alive=self.region_alive)
            except RuntimeError:  # every region dark: degrade to on-demand
                self.stats.degraded_jobs += 1
                self._run_ondemand(Job(self._job_counter, self._t,
                                       work_steps=1, pool=home))
                return
        job = Job(self._job_counter, self._t, work_steps=1, pool=target)
        region = self.topology.regions[target]
        qlen_t = len(self.queues[target])
        if (qlen_t < region.rmax
                and self.ctl.admit(qlen_t, self.rng)):
            self.queues[target].append(job)
            self.stats.region_routed[target] += 1
            if target != home:
                self.stats.cross_region += 1
        else:
            self._run_ondemand(job)
        if self.tracer is not None:
            self.tracer.record(self._t, "job", loc=target,
                               qlen=sum(self.qlen_region()))

    def _spot_arrival(self, region_idx: int) -> None:
        if not self.region_alive[region_idx]:
            return  # dark region: the slot never materializes
        queue = self.queues[region_idx]
        if self.tracer is not None:
            self.tracer.record(
                self._t, "spot", loc=region_idx,
                qlen=sum(self.qlen_region()) - (1 if queue else 0),
                **({"wait": self._t - queue[0].arrival_time}
                   if queue else {}))
        if not queue:
            return
        job = queue.popleft()  # FIFO within the region partition
        region = self.topology.regions[region_idx]
        delay = self._t - job.arrival_time
        self.stats.jobs_completed += 1
        self.stats.spot_served += 1
        self.stats.region_served[region_idx] += 1
        self.stats.total_cost += region.price
        self.stats.spot_cost += region.price
        self.stats.total_delay += delay
        self.ctl.on_job_complete(delay)

    def _preempt_event(self, region_idx: int) -> None:
        """Hazard-clock revocation, the PR-2 recovery model per region."""
        queue = self.queues[region_idx]
        if self.tracer is not None:
            self.tracer.record(self._t, "preempt", loc=region_idx,
                               qlen=sum(self.qlen_region())
                               - (1 if queue else 0))
        if not queue:
            return  # the revoked instance was idle
        job = queue.popleft()
        region = self.topology.regions[region_idx]
        delay = self._t - job.arrival_time
        self.stats.preemptions += 1
        self.stats.total_cost += region.price
        self.stats.spot_cost += region.price
        within = checkpoint_within_notice(self.checkpoint_hours,
                                          region.notice)
        if within:
            self.stats.checkpoints += 1
        admitted = within and self.ctl.admit(len(queue), self.rng)
        extra = 0.0
        if within and not admitted and self.retry is not None:
            admitted, extra = _retry_admit(self.ctl, self.rng, self.retry,
                                           len(queue), self.stats)
        if admitted:
            self.stats.restores += 1
            queue.append(dataclasses.replace(job, arrival_time=self._t))
            self.stats.total_delay += delay + extra
            self.stats.jobs_completed += 1  # leg accounting
            self.ctl.on_job_complete(delay + extra)
        else:
            self._run_ondemand(job, extra_delay=delay + extra)

    def _run_ondemand(self, job: Job, extra_delay: float = 0.0) -> None:
        del job
        self.stats.jobs_completed += 1
        self.stats.ondemand_served += 1
        self.stats.total_cost += self.k
        self.stats.total_delay += extra_delay
        self.ctl.on_job_complete(extra_delay)

    # ---------------------------------------------------- on-device what-if
    def what_if_sweep(self, rs, *, n_events: int = 20_000, n_seeds: int = 2,
                      k=None, key=None, choice: str | None = None,
                      telemetry=None, shard: str = "none", mesh=None) -> dict:
        """Sweep admission knobs against THIS cluster's topology, on-device.

        Runs :func:`repro.core.engine.run_region_sweep` with the cluster's
        topology, routing rule, and recovery parameters — one compiled
        program for the whole what-if grid, not a host loop.  ``telemetry=``
        forwards a :class:`repro.obs.Telemetry` so the grid also reports
        P50/P99 waits and per-region counters.  ``shard="lanes"`` (with an
        optional ``mesh=``) partitions the what-if grid's lane axis across
        local devices — same contract as the engine entry points.
        """
        import jax
        import jax.numpy as jnp

        from repro.core.engine import run_region_sweep
        from repro.core.regions import RoutingKernel

        with EntrySpan("repro.cluster.what_if_sweep[region]"):
            if key is None:
                key = jax.random.key(int(self.rng.integers(2**31)))
            kern = RoutingKernel(
                NoticeAwareKernel(checkpoint_time=self.checkpoint_hours),
                choice=self.route if choice is None else choice)
            # the engine joins this call's span and phases
            return run_region_sweep(
                self.topology, kern, {"r": jnp.asarray(rs, jnp.float32)},
                k=self.k if k is None else k, n_events=n_events, key=key,
                n_seeds=n_seeds, telemetry=telemetry, shard=shard, mesh=mesh,
            )
