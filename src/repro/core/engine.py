"""Policy-generic, vmap-batched G/G/1+spot sweep engine.

One merged-renewal event loop replaces the two near-duplicate simulators the
seed carried (``run_queue_sim`` / ``run_single_slot_sim``): the loop is
parameterized by a traced **policy kernel** and the two paper policies become
small kernel implementations (:class:`repro.core.policies.ThreePhaseKernel`,
:class:`repro.core.policies.SingleSlotKernel`).

Policy-kernel protocol
----------------------
(Full reference: docs/kernels.md — all four hooks, tie order, worked
example.)  A kernel is a hashable (frozen-dataclass) static object with
one traced hook::

    admit(params, qlen, key) -> (admit: bool[], budget: f32[])

called once per merged event with the *pre-event* queue length and a fresh
PRNG subkey.  On a job-arrival event the engine admits the job iff
``admit & (qlen < rmax)`` and stamps it with the returned *wait budget*
(``on_join``): the maximal time the job will wait for a spot slot.  A budget
of :data:`INF` means "wait indefinitely" (Theorem 4); a finite budget arms a
**defect-on-deadline** event — when it expires the job leaves the queue for
an on-demand instance (cost ``k``, delay = its age), exactly the Theorems-2/3
maximal-wait semantics.  ``params`` is an arbitrary traced pytree (the
admission knob ``r``, wait-time parameters, …) so a whole parameter grid can
be ``vmap``-ed without retracing.

Queue representation
--------------------
A slot-mask ring: ``ages``/``budgets``/``order`` arrays of static size
``rmax`` plus an occupancy mask.  Spot slots serve the FIFO-oldest occupied
slot (min join ``order``); deadlines fire on the slot with the smallest
remaining budget.  This is O(rmax) per event — the same as the seed's ring
buffer — but supports out-of-order departures, which a head/tail ring cannot.
``order``/``next_seq`` are int32, rebased to the oldest occupied sequence at
every window boundary (:func:`_rebase_order`), so admission counts are
unbounded.

Event-time ties (measure-zero for continuous samplers) resolve
spot > deadline > job, matching the seed's single-slot simulator.

Numerics
--------
Ages are relative (incremented by the inter-event gap ``dt``), never absolute
event times, so float32 precision does not degrade over long horizons.  Sums
are accumulated in float32 **per chunk** (:func:`run_chunked` re-zeros the
accumulator every ``chunk_events`` events) and assembled in float64 on the
host by :func:`summarize` — a multi-billion-event horizon loses no more
precision than its last chunk.  With a single chunk the engine reproduces the
seed simulators bit-for-bit per seed (verified in tests/test_core_engine.py
against frozen reference copies of the seed event bodies).

Batched sweeps
--------------
:func:`run_sweep` broadcasts a params pytree + cost ratio ``k`` to a common
grid shape, pairs it with ``n_seeds`` common-random-number seeds, and runs
the whole (grid × seeds) fleet as ONE jitted nested-``vmap`` program — no
per-point Python dispatch, no retracing.  Cost accounting (paper §II): spot
service costs 1, an on-demand dispatch costs ``k``; π₀ is tracked both
time-averaged and as the fraction of spot arrivals finding the queue empty
(the quantity Theorem 1's proof uses).

Executors (``impl=``)
---------------------
Every entry point dispatches between executors sharing the same traced
event bodies: ``impl="xla"`` is the nested-vmap ``lax.scan`` program above;
``impl="pallas"`` hands the fleet to the batched-event kernel in
:mod:`repro.kernels.sweep` — engine state laid out lane-last as
(rmax, tile) VMEM blocks (market clocks as (n_pools, tile)) resident
across a whole float32 window of events, with the clock merge, slot reductions, and one-hot
updates fused into one kernel body instead of N width-``rmax`` HLO selects
re-read from HBM per event; ``impl="ref"`` is the kernel's pure-JAX scan
reference on the identical lane layout.  Bit-for-bit contract
(tests/test_sweep_kernel.py): pallas == ref to the last bit on every
config and tile size; against the ``"xla"`` executor, integer event
accounting is bitwise identical and float32 window sums match to ~1 ulp
(the XLA executor keeps a broadcast-nested batch layout that is ~2.5×
faster on CPU but whose transcendental codegen can round an ulp apart —
see EXPERIMENTS.md).  ``interpret=None`` compiles the kernel with Mosaic
on a TPU and runs the Pallas interpreter elsewhere; the compiled kernel
runs ``rng="slab"`` only (``rng="split"`` raises).

Randomness (``rng=``)
---------------------
Every entry point also dispatches between two PRNG streams (PR 5; full
story in EXPERIMENTS.md §"Event-loop RNG" and :mod:`repro.core.clocks`):
``rng="split"`` (default) is the frozen per-event split/fold_in ladder the
seed wrappers and every bitwise contract are pinned to; ``rng="slab"``
generates one ``(window_events, n_cols)`` uint32 slab per float32 window
with a single counter-based threefry call and has the event body consume
draws by static column index — no per-event key arithmetic, the per-pool/
per-region Poisson preemption clock vectors collapsed to one scalar clock
at the superposed total hazard (exact, by the superposition theorem), and,
in the Pallas executor, the slab arriving as a plain VMEM input block per
window (zero in-kernel RNG).  The slab stream holds the pallas == ref ==
xla integer-accounting ledger on its own terms; slab-vs-split equivalence
is distributional (KS-tested in tests/test_event_rng.py), so ``"slab"`` is
the stream for new sweeps and ``"split"`` the compatibility stream.

Telemetry (``telemetry=``)
--------------------------
Every entry point dispatches a third static axis (PR 7; full story in
docs/observability.md and :mod:`repro.obs`): ``telemetry=None`` (default)
compiles exactly today's program — the telemetry branch of every event
body is statically absent, so the off path is *bitwise* the pre-telemetry
engine on all three loops × all three executors (frozen in
tests/test_obs.py).  With a :class:`repro.obs.Telemetry` descriptor the
stats pytree becomes a ``(base, telemetry)`` pair riding through the same
scanners/kernels (both are generic over the stats pytree), and the event
bodies additionally fold each event into streaming log-binned wait/cost
histograms (mergeable quantile sketches → P50/P99 per grid point),
event-type counters, per-pool/per-region defect/resume counters, and —
with ``trace_cap > 0`` — a bounded per-window event ring exportable to
Chrome/Perfetto JSON (:mod:`repro.obs.trace`).  The base statistics are
accumulated by the untouched expressions, so telemetry-on primary stats
equal telemetry-off stats exactly; the summaries only gain new fields.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arrivals import ArrivalProcess
from repro.core.clocks import (SlabLayout, argmin_first, build_slab_layout,
                               hazard_clock, lane_slab_keys, process_udim,
                               sample_clock_vector, sample_hazard_clocks,
                               split_event_keys, synth_key, tagged_keys,
                               thinning_pick, window_slab)
from repro.core.env import (EnvState, EnvTimeline, clock_rescale, env_row,
                            init_env_state, inv_avail)
from repro.core.market import (PoolState, SpotMarket, as_market,
                               checkpoint_within_notice)
from repro.core.policies import deadline_slack
from repro.core.regions import RegionTopology, RegionView, as_topology
from repro.core.work import WorkModel, WorkState, init_work_state
from repro.distributed.sharding import (lane_mesh, lane_spec, pad_lanes,
                                        shard_map_1d)
from repro.obs.shocks import env_update, env_zeros, summarize_env
from repro.obs.survival import (summarize_survival, survival_update,
                                survival_zeros)
from repro.kernels.sweep import (batched_events, batched_event_windows_ref,
                                 default_interpret)
from repro.obs.stats import (Telemetry, summarize_telemetry,
                             telemetry_update, telemetry_zeros)
from repro.obs.timing import EntrySpan

# numpy (not jnp) scalars: they inline as jaxpr literals, so the event
# bodies stay capture-free inside the Pallas kernel trace (device-array
# constants would be hoisted as consts, which pallas_call rejects)
INF = np.float32(3e38)
_ORDER_MAX = np.int32(2**31 - 1)

#: One chunk_events default for every entry point (run_sim, run_sweep,
#: run_market_sim, run_market_sweep): float32 window sums are re-zeroed
#: every 2**16 events and assembled in float64 by :func:`summarize`, so the
#: precision behavior of a horizon does not depend on which entry point ran
#: it.  Horizons ≤ DEFAULT_CHUNK_EVENTS still accumulate in a single window
#: (chunks are clamped to ``n_events``), which keeps the seed's bit-for-bit
#: contract for short runs; pass ``chunk_events=None`` to force one window
#: at any horizon.
DEFAULT_CHUNK_EVENTS = 1 << 16


@runtime_checkable
class PolicyKernel(Protocol):
    """Static, hashable policy plugged into the engine's event loop."""

    def admit(self, params, qlen: jax.Array, key: jax.Array
              ) -> tuple[jax.Array, jax.Array]:
        """Return (admit?, wait budget) for a job arriving at ``qlen``."""
        ...


class WindowStats(NamedTuple):
    """Per-window accumulators (float32 sums / int32 counts)."""

    jobs_arrived: jax.Array
    jobs_completed: jax.Array
    spot_served: jax.Array
    ondemand: jax.Array
    cost_sum: jax.Array
    delay_sum: jax.Array
    time_elapsed: jax.Array
    empty_time: jax.Array
    spot_arrivals: jax.Array
    spot_found_empty: jax.Array

    @staticmethod
    def zeros() -> "WindowStats":
        z = jnp.zeros((), jnp.float32)
        zi = jnp.zeros((), jnp.int32)
        return WindowStats(zi, zi, zi, zi, z, z, z, z, zi, zi)


class EngineState(NamedTuple):
    key: jax.Array
    next_job: jax.Array  # time until next job arrival
    next_spot: jax.Array  # time until next spot-slot arrival
    ages: jax.Array  # (rmax,) time each queued job has waited
    budgets: jax.Array  # (rmax,) remaining wait budget (INF = wait forever)
    occ: jax.Array  # (rmax,) bool occupancy mask
    order: jax.Array  # (rmax,) int32 join sequence number
    next_seq: jax.Array  # int32 next join sequence number
    qlen: jax.Array  # int32 number of queued jobs


def init_engine_state(key: jax.Array, job: ArrivalProcess,
                      spot: ArrivalProcess, rmax: int,
                      ep: dict | None = None) -> EngineState:
    kj, ks, kc = jax.random.split(key, 3)
    next_job = job.sample(kj)
    next_spot = spot.sample(ks)
    if ep is not None:
        # initial spot clock runs under segment 0's availability
        next_spot = next_spot * inv_avail(ep["avail"][0])[0]
    return EngineState(
        key=kc,
        next_job=next_job,
        next_spot=next_spot,
        ages=jnp.zeros((rmax,), jnp.float32),
        budgets=jnp.full((rmax,), INF, jnp.float32),
        occ=jnp.zeros((rmax,), jnp.bool_),
        order=jnp.zeros((rmax,), jnp.int32),
        next_seq=jnp.zeros((), jnp.int32),
        qlen=jnp.zeros((), jnp.int32),
    )


def _admit_slab(kernel, params, qlen, layout: SlabLayout, x):
    """Slab-mode admission: a slab-aware kernel consumes its own uniform
    columns (``admit_u``); a legacy kernel gets a key synthesized from two
    raw columns and draws in-body (the compatibility path)."""
    if layout.admit_mode == "u":
        return kernel.admit_u(params, qlen, layout.uniforms(x, layout.admit))
    return kernel.admit(params, qlen, synth_key(layout.bits(x, layout.admit)))


def _engine_event(job: ArrivalProcess, spot: ArrivalProcess,
                  kernel: PolicyKernel, rmax: int,
                  layout: SlabLayout | None, carry: EngineState,
                  stats: WindowStats, params, k_cost: jax.Array,
                  x: jax.Array | None = None, tel: Telemetry | None = None,
                  ep: dict | None = None, work: WorkModel | None = None,
                  wk: dict | None = None
                  ) -> tuple[EngineState, WindowStats]:
    """Process one merged event (job arrival / spot slot / wait deadline).

    Per-slot updates are dense one-hot selects rather than scatter/gather:
    under ``vmap`` a traced-index ``.at[i].set`` lowers to a scatter, which
    is far slower on CPU/TPU than the width-``rmax`` selects used here (and
    the selects are numerically identical).

    ``layout=None`` is the frozen ``rng="split"`` stream (per-event key
    ladder); with a :class:`SlabLayout`, ``x`` is this event's uint32 slab
    row and the body performs no key arithmetic at all.

    ``tel`` (static) switches ``stats`` to a ``(base, telemetry)`` pair;
    the base expressions are untouched, the telemetry fold is a pure
    appendage over locals the body already computed (the module
    docstring's zero-cost-off / primary-stats-unchanged contract).

    ``ep`` (traced; see :mod:`repro.core.env`) switches ``carry`` to an
    ``(EngineState, EnvState)`` pair and ``stats`` to an outermost
    ``(stats, EnvWindowStats)`` pair: segment boundaries join the clock
    race as a highest-priority event, current-segment multipliers scale
    the spot price and supply, and survived clocks are rescaled exactly
    at each crossing.  A single open-ended segment reproduces the
    ``ep=None`` arithmetic bit-for-bit (every mask statically False-
    valued, every multiplier exactly 1.0).

    ``work`` (static :class:`~repro.core.work.WorkModel`) + ``wk`` (its
    traced params dict) switch ``carry`` to an *outermost*
    ``(carry, WorkState)`` pair and ``stats`` to an outermost
    ``(stats, SurvivalWindowStats)`` pair: every served unit pays down
    restart-overhead debt before making progress, a serve completes the
    job only when its remaining total clears, and the survival ledger
    gains job-level admission/finish/deadline-miss accounting.  The
    single-queue loop has no preemption, so rollback never fires here;
    the identity model (``WorkModel()``) makes every serve final and the
    base statistics bit-for-bit today's.
    """
    if work is not None:
        carry, wk_c = carry
        stats, wstats = stats
    if ep is not None:
        carry, env_c = carry
        stats, estats = stats
        seg = env_c.seg
        avail_row = env_row(ep["avail"], seg)
    if tel is not None:
        stats, tstats = stats
    if layout is None:
        key, k_job, k_spot, k_pol, _, _ = split_event_keys(carry.key)
    else:
        key = carry.key  # advanced once per window by the slab generator
    iota = jax.lax.iota(jnp.int32, rmax)

    budgets_masked = jnp.where(carry.occ, carry.budgets, INF)
    if work is not None and getattr(kernel, "safety_net", False):
        # can't-be-late watchdog: a job's panic time is the latest instant
        # still compatible with finishing on demand by its deadline
        # (deadline_slack); merging it into the budget race reuses the
        # defect-on-deadline machinery wholesale, so a panic IS a
        # defection to on-demand — just one forced early enough to land
        # on time.  Clamped at 0: an already-doomed job defects at the
        # next event rather than arming a negative clock.
        buf = np.float32(getattr(kernel, "slack_buffer", 0.0))
        rem_tot_all = wk_c.oh + jnp.maximum(wk["total_work"] - wk_c.prog,
                                            0.0)
        panic_at = jnp.maximum(
            deadline_slack(wk["deadline"], wk_c.life, rem_tot_all,
                           wk["od_time"], buf), 0.0)
        panic_at = jnp.where(carry.occ, panic_at, INF)
        panic_armed = panic_at < budgets_masked
        budgets_masked = jnp.minimum(budgets_masked, panic_at)
    else:
        panic_armed = None
    deadline = jnp.min(budgets_masked)
    defect_slot = argmin_first(budgets_masked)

    dt = jnp.minimum(jnp.minimum(carry.next_job, carry.next_spot), deadline)
    is_spot = carry.next_spot <= jnp.minimum(carry.next_job, deadline)
    is_deadline = (~is_spot) & (deadline <= carry.next_job)
    is_job = (~is_spot) & (~is_deadline)
    if ep is not None:
        # boundary-as-event: the segment boundary wins the race outright
        # (no queue activity; clocks age by dt), so dt never spans
        # segments.  With one open-ended segment next_boundary is 3e38:
        # is_boundary is identically False and dt is unchanged bitwise.
        is_boundary = env_c.next_boundary <= dt
        dt = jnp.minimum(dt, env_c.next_boundary)
        not_b = ~is_boundary
        is_spot = is_spot & not_b
        is_deadline = is_deadline & not_b
        is_job = is_job & not_b

    ages = carry.ages + dt
    budgets = jnp.where(carry.occ, carry.budgets - dt, INF)

    # ---- job arrival: ask the policy kernel ----
    if layout is None:
        admit_raw, budget = kernel.admit(params, carry.qlen, k_pol)
    else:
        admit_raw, budget = _admit_slab(kernel, params, carry.qlen, layout, x)
    admit = is_job & admit_raw & (carry.qlen < rmax)
    od_now = is_job & (~admit)  # rejected -> immediate on-demand, delay 0
    join_slot = argmin_first(carry.occ.astype(jnp.int32))  # first free slot

    # ---- spot slot: serve the FIFO-oldest job ----
    serve_slot = argmin_first(jnp.where(carry.occ, carry.order, _ORDER_MAX))
    has_job = carry.qlen > 0
    served = is_spot & has_job
    wait_served = jnp.sum(jnp.where(iota == serve_slot, ages, 0.0))

    if work is not None:
        # one unit of service pays down restart-overhead debt first and
        # spills the remainder into real progress; the serve *completes*
        # the job only when it clears the remaining total.  A partial
        # serve keeps the slot occupied with its join order (the FIFO
        # argmin keeps picking it), pays the spot price, and counts as a
        # leg in the base stats — the paper's renewal accounting is
        # untouched; job-level truth lives in the survival ledger.
        serve_vec = served & (iota == serve_slot)
        rem_tot = wk_c.oh + (wk["total_work"] - wk_c.prog)
        rem_serve = jnp.sum(jnp.where(iota == serve_slot, rem_tot, 0.0))
        oh_new = jnp.where(serve_vec, jnp.maximum(wk_c.oh - 1.0, 0.0),
                           wk_c.oh)
        spill = jnp.maximum(1.0 - wk_c.oh, 0.0)
        prog_new = jnp.where(
            serve_vec, jnp.minimum(wk_c.prog + spill, wk["total_work"]),
            wk_c.prog)
        done_inc = jnp.sum(jnp.where(serve_vec, prog_new - wk_c.prog, 0.0))
        if work.ckpt == "periodic":
            take_vec = (serve_vec & (rem_tot > 1.0)
                        & (prog_new - wk_c.ckpt >= wk["ckpt_period"]))
            ckpt_new = jnp.where(take_vec, prog_new, wk_c.ckpt)
            oh_new = oh_new + jnp.where(take_vec, wk["ckpt_cost"], 0.0)
            ckpt_taken = jnp.any(take_vec)
        else:
            ckpt_new = wk_c.ckpt
            ckpt_taken = jnp.zeros((), jnp.bool_)
        complete_serve = served & (rem_serve <= 1.0)
    else:
        complete_serve = served

    # ---- deadline: the minimal-budget job defects to on-demand ----
    defected = is_deadline  # deadline < INF implies an occupied slot
    age_defect = jnp.sum(jnp.where(iota == defect_slot, ages, 0.0))

    leave = complete_serve | defected
    leave_slot = jnp.where(served, serve_slot, defect_slot)

    join_mask = admit & (iota == join_slot)
    leave_mask = leave & (iota == leave_slot)
    ages = jnp.where(join_mask, 0.0, ages)
    budgets = jnp.where(join_mask, budget, budgets)
    occ = (carry.occ | join_mask) & (~leave_mask)
    order = jnp.where(join_mask, carry.next_seq, carry.order)
    if work is not None:
        life_new = jnp.where(join_mask, 0.0, wk_c.life + dt)
        prog_new = jnp.where(join_mask, 0.0, prog_new)
        oh_new = jnp.where(join_mask, 0.0, oh_new)
        ckpt_new = jnp.where(join_mask, 0.0, ckpt_new)

    if layout is None:
        job_draw = job.sample(k_job)
        spot_draw = spot.sample(k_spot)
    else:
        job_draw = job.sample_u(layout.uniforms(x, layout.job))
        spot_draw = spot.sample_u(layout.uniforms(x, layout.spot))
    next_job = jnp.where(is_job, job_draw, carry.next_job - dt)
    next_spot = jnp.where(is_spot, spot_draw, carry.next_spot - dt)
    if ep is not None:
        # supply side: the spot clock runs at rate·avail, represented as
        # base-draw × 1/avail (blackouts inflate by BLACKOUT_SCALE, kept
        # finite).  Fresh draws use the post-event segment; a boundary
        # re-expresses the survived clock under the new rate — in this
        # representation a uniform × inv_new/inv_old, valid through
        # blackouts in either direction.  Demand (the job clock) is not
        # modulated.  All factors are exactly 1.0 on a constant timeline.
        seg_new = seg + is_boundary.astype(jnp.int32)
        inv_old = inv_avail(avail_row)[0]
        inv_new = inv_avail(env_row(ep["avail"], seg_new))[0]
        next_spot = jnp.where(is_spot, spot_draw * inv_new, next_spot)
        next_spot = jnp.where(is_boundary, next_spot * (inv_new / inv_old),
                              next_spot)
    new_carry = EngineState(
        key=key,
        next_job=next_job,
        next_spot=next_spot,
        ages=ages,
        budgets=budgets,
        occ=occ,
        order=order,
        next_seq=carry.next_seq + jnp.where(admit, 1, 0),
        qlen=carry.qlen + jnp.where(admit, 1, 0) - jnp.where(leave, 1, 0),
    )
    if ep is None:
        # deferred so the op traces at its original position inside the
        # stats constructor (the frozen-lowering contract is byte-exact)
        cost_served = lambda: jnp.where(served, 1.0, 0.0)  # noqa: E731
    else:
        # spot price modulation: serves pay price_mult(seg) per unit;
        # the on-demand premium k_cost is the stable fallback price and
        # is NOT spiked (spikes are a spot-market phenomenon)
        cost_served = lambda: jnp.where(  # noqa: E731
            served, env_row(ep["price"], seg)[0], 0.0)
    new_stats = WindowStats(
        jobs_arrived=stats.jobs_arrived + is_job.astype(jnp.int32),
        jobs_completed=stats.jobs_completed
        + (od_now | served | defected).astype(jnp.int32),
        spot_served=stats.spot_served + served.astype(jnp.int32),
        ondemand=stats.ondemand + (od_now | defected).astype(jnp.int32),
        cost_sum=stats.cost_sum
        + cost_served()
        + jnp.where(od_now | defected, k_cost, 0.0),
        delay_sum=stats.delay_sum
        + jnp.where(served, wait_served, 0.0)
        + jnp.where(defected, age_defect, 0.0),
        time_elapsed=stats.time_elapsed + dt,
        empty_time=stats.empty_time + jnp.where(carry.qlen == 0, dt, 0.0),
        spot_arrivals=stats.spot_arrivals + is_spot.astype(jnp.int32),
        spot_found_empty=stats.spot_found_empty
        + (is_spot & (~has_job)).astype(jnp.int32),
    )
    if tel is not None:
        false = jnp.zeros((), jnp.bool_)
        tstats = telemetry_update(
            tel, tstats, t=new_stats.time_elapsed, is_job=is_job,
            is_spot=is_spot, is_pre=false, is_deadline=is_deadline,
            served=served, resume=false, defected=defected, od_now=od_now,
            wait_sample=jnp.where(served, wait_served, age_defect),
            wait_valid=served | defected,
            cost_inc=jnp.where(served, np.float32(1.0), k_cost),
            cost_valid=served | od_now | defected,
            loc=jnp.zeros((), jnp.int32), n_locs=1, qlen=new_carry.qlen)
    out_stats = (new_stats, tstats) if tel is not None else new_stats
    out_carry = new_carry
    if ep is not None:
        estats = env_update(
            estats, is_boundary=is_boundary,
            kind_prev=env_row(ep["kind"], seg),
            kind_next=env_row(ep["kind"], seg_new), dt=dt, is_job=is_job,
            od_now=od_now, served=served, resumed=jnp.zeros((), jnp.bool_))
        new_env = EnvState(
            next_boundary=jnp.where(
                is_boundary,
                env_row(ep["t_end"], seg_new) - env_row(ep["t_end"], seg),
                env_c.next_boundary - dt),
            seg=seg_new)
        out_carry = (new_carry, new_env)
        out_stats = (out_stats, estats)
    if work is not None:
        life_def = jnp.sum(jnp.where(iota == defect_slot, wk_c.life + dt,
                                     0.0))
        rem_def = jnp.sum(jnp.where(iota == defect_slot, rem_tot, 0.0))
        life_srv = jnp.sum(jnp.where(iota == serve_slot, wk_c.life + dt,
                                     0.0))
        od = wk["od_time"]
        # hard deadline-miss accounting: a job finishes at its last served
        # unit, or when it migrates to on-demand (od finish time = life at
        # migration + remaining work × od_time — live migration, the
        # can't-be-late convention)
        miss = ((od_now & (wk["total_work"] * od > wk["deadline"]))
                | (defected & (life_def + rem_def * od > wk["deadline"]))
                | (complete_serve & (life_srv > wk["deadline"])))
        panic = (defected & jnp.any((iota == defect_slot) & panic_armed)
                 if panic_armed is not None else jnp.zeros((), jnp.bool_))
        zf = jnp.zeros((), jnp.float32)
        wstats = survival_update(
            wstats, admitted=is_job,
            finished=od_now | complete_serve | defected, missed=miss,
            checkpoint=ckpt_taken, panic=panic, work_done=done_inc,
            work_lost=zf, work_recomputed=zf, overhead_paid=zf)
        return (out_carry, WorkState(prog=prog_new, oh=oh_new,
                                     ckpt=ckpt_new, life=life_new)), \
            (out_stats, wstats)
    return out_carry, out_stats


def _rebase_order(state):
    """Rebase join sequence numbers to the oldest occupied slot.

    ``order``/``next_seq`` are int32 and grow by one per admission; an
    unbounded counter wraps after ~2.1e9 admissions (well inside a long
    adaptive horizon), turning the FIFO ``argmin`` against ``_ORDER_MAX``
    into newest-first.  Subtracting the minimum *occupied* sequence (or
    ``next_seq`` itself when the queue is empty) at every window boundary
    keeps the counter below window-events + rmax forever.  The shift is
    uniform across occupied slots, so every order comparison — and therefore
    every statistic — is bitwise unchanged; works on any state carrying
    ``occ``/``order``/``next_seq`` (EngineState and MarketState).
    """
    base = jnp.min(jnp.where(state.occ, state.order, state.next_seq))
    return state._replace(
        order=jnp.where(state.occ, state.order - base, 0),
        next_seq=state.next_seq - base,
    )


def _rebase_order_env(state):
    """:func:`_rebase_order` for an ``(engine-state, EnvState)`` pair —
    the window-boundary epilogue when the env axis is on (the timeline
    cursor crosses windows untouched)."""
    base, env_c = state
    return (_rebase_order(base), env_c)


def _rebase_order_any(state):
    """:func:`_rebase_order` through arbitrary ``((state, env?), work?)``
    nesting — the window-boundary epilogue when the work axis is on (env
    cursor and work structure cross windows untouched)."""
    if hasattr(state, "occ"):
        return _rebase_order(state)
    return (_rebase_order_any(state[0]),) + tuple(state[1:])


def _rebase_for(ep, work):
    """Window-boundary rebase epilogue for the active (env, work) axes.

    Returns the exact pre-work function objects when ``work`` is off, so
    the ``work=None`` program is the identical jaxpr it always was."""
    if work is not None:
        return _rebase_order_any
    return _rebase_order if ep is None else _rebase_order_env


def _base_key_state(state):
    """Innermost engine state of an arbitrarily wrapped (env/work) pair."""
    while not hasattr(state, "key"):
        state = state[0]
    return state


def _replace_base_key(state, key):
    """Swap the lane key on the innermost engine state, preserving the
    surrounding (env/work) tuple nesting."""
    if hasattr(state, "key"):
        return state._replace(key=key)
    return (_replace_base_key(state[0], key),) + tuple(state[1:])


def _scan_window(step, zeros, state, n_events: int):
    """Scan ``step`` for ``n_events`` events from fresh window accumulators.

    Generic over the (state, stats) pytree pair — the PR-1 single-spot loop
    and the market loop share this scanner (and :func:`_scan_chunked`), so
    the chunked float32-window numerics are identical across both paths.
    """

    def body(sc, _):
        c, s = step(sc[0], sc[1])
        return (c, s), None

    (state, stats), _ = jax.lax.scan(body, (state, zeros), None,
                                     length=n_events)
    return state, stats


def _scan_chunked(step, zeros, state, n_events: int, chunk_events: int,
                  rebase=_rebase_order):
    """Run exactly ``n_events`` events as stacked float32 chunk windows.

    Every window boundary rebases the join-sequence counters
    (:func:`_rebase_order` — or :func:`_rebase_order_env` when the state
    is an env pair) so int32 ``order``/``next_seq`` never wrap on long
    horizons; the Pallas kernel path applies the same epilogue, so the
    two impls carry bitwise-identical state between windows.
    """
    n_chunks, rem = divmod(n_events, chunk_events)

    def chunk(c, _):
        c, s = _scan_window(step, zeros, c, chunk_events)
        return rebase(c), s

    state, stats = jax.lax.scan(chunk, state, None, length=n_chunks)
    if rem:
        state, tail = _scan_window(step, zeros, state, rem)
        state = rebase(state)
        stats = jax.tree.map(
            lambda s, t: jnp.concatenate([s, t[None]]), stats,
            jax.tree.map(jnp.asarray, tail),
        )
    return state, stats


def _scan_window_slab(step, zeros, state, n_events: int, n_cols: int,
                      paired: bool = False):
    """Slab-stream window: ONE counter-based bits call generates the whole
    window's ``(n_events, n_cols)`` uint32 slab, the event scan consumes it
    row by row as ``xs``, and the lane key advances once per window (not
    per event).  :func:`repro.core.clocks.lane_slab_keys` walks the same
    key ladder, so the Pallas/ref executors consume bitwise-identical
    slab rows.

    ``paired`` flags a tuple-wrapped state — ``(engine, EnvState)`` when
    the env axis is on, and/or an outermost ``(state, WorkState)`` when
    the work axis is on (NamedTuples are tuples, so this cannot be
    sniffed) — the slab ladder walks the innermost engine state's key
    either way."""
    if paired:
        key, slab = window_slab(_base_key_state(state).key, n_events, n_cols)
        state = _replace_base_key(state, key)
    else:
        key, slab = window_slab(state.key, n_events, n_cols)
        state = state._replace(key=key)

    def body(sc, x):
        c, s = step(sc[0], sc[1], x)
        return (c, s), None

    (state, stats), _ = jax.lax.scan(body, (state, zeros), slab)
    return state, stats


def _scan_chunked_slab(step, zeros, state, n_events: int, chunk_events: int,
                       n_cols: int, paired: bool = False,
                       rebase=_rebase_order):
    """Slab-stream twin of :func:`_scan_chunked` (same chunk plan, same
    per-window order rebase)."""
    n_chunks, rem = divmod(n_events, chunk_events)

    def chunk(c, _):
        c, s = _scan_window_slab(step, zeros, c, chunk_events, n_cols,
                                 paired=paired)
        return rebase(c), s

    state, stats = jax.lax.scan(chunk, state, None, length=n_chunks)
    if rem:
        state, tail = _scan_window_slab(step, zeros, state, rem, n_cols,
                                        paired=paired)
        state = rebase(state)
        stats = jax.tree.map(
            lambda s, t: jnp.concatenate([s, t[None]]), stats,
            jax.tree.map(jnp.asarray, tail),
        )
    return state, stats


def _window_plan(n_events: int, chunk_events: int,
                 burn_in: int) -> tuple[int, ...]:
    """Static per-window event counts: [burn-in?] + full chunks + [tail?]."""
    full, rem = divmod(n_events, chunk_events)
    return (((burn_in,) if burn_in else ()) + (chunk_events,) * full
            + ((rem,) if rem else ()))


def _raw_keys(keys: jax.Array) -> jax.Array:
    """Typed PRNG keys -> raw uint32 key data (Pallas refs carry raw words);
    threefry on the raw words is bitwise the typed-key stream."""
    if jnp.issubdtype(keys.dtype, jax.dtypes.prng_key):
        return jax.random.key_data(keys)
    return keys


def _engine_layout(job: ArrivalProcess, spot: ArrivalProcess,
                   kernel) -> SlabLayout:
    """Slab column map for the single-queue loop (built at trace time)."""
    return build_slab_layout(kernel, job_udim=process_udim(job),
                             spot_udim=process_udim(spot))


def _with_zeros(zeros, tel: Telemetry | None, n_locs: int,
                env: bool = False, work: bool = False):
    """Pair base window zeros with telemetry zeros when that axis is on,
    then with shock-counter zeros when the env axis is on, then
    (outermost) with survival-ledger zeros when the work axis is on."""
    if tel is not None:
        zeros = (zeros, telemetry_zeros(tel, n_locs))
    if env:
        zeros = (zeros, env_zeros())
    if work:
        zeros = (zeros, survival_zeros())
    return zeros


def run_window(job: ArrivalProcess, spot: ArrivalProcess,
               kernel: PolicyKernel, rmax: int, state: EngineState, params,
               k_cost: jax.Array, n_events: int,
               layout: SlabLayout | None = None,
               tel: Telemetry | None = None, ep: dict | None = None,
               work: WorkModel | None = None, wk: dict | None = None
               ) -> tuple[EngineState, WindowStats]:
    """Run ``n_events`` merged events; return state + one window of sums."""
    step = functools.partial(_engine_event, job, spot, kernel, rmax, layout,
                             params=params, k_cost=k_cost, tel=tel, ep=ep,
                             work=work, wk=wk)
    zeros = _with_zeros(WindowStats.zeros(), tel, 1, env=ep is not None,
                        work=work is not None)
    if layout is None:
        return _scan_window(lambda c, s: step(c, s), zeros, state, n_events)
    return _scan_window_slab(lambda c, s, x: step(c, s, x=x), zeros, state,
                             n_events, layout.n_cols,
                             paired=(ep is not None) or (work is not None))


def run_chunked(job: ArrivalProcess, spot: ArrivalProcess,
                kernel: PolicyKernel, rmax: int, state: EngineState, params,
                k_cost: jax.Array, n_events: int, chunk_events: int,
                layout: SlabLayout | None = None,
                tel: Telemetry | None = None, ep: dict | None = None,
                work: WorkModel | None = None, wk: dict | None = None
                ) -> tuple[EngineState, WindowStats]:
    """Run exactly ``n_events`` events as stacked float32 chunk windows.

    Returns stats with a leading chunk axis; :func:`summarize` reduces it in
    float64 so long horizons do not hit float32 sum saturation.
    """
    step = functools.partial(_engine_event, job, spot, kernel, rmax, layout,
                             params=params, k_cost=k_cost, tel=tel, ep=ep,
                             work=work, wk=wk)
    zeros = _with_zeros(WindowStats.zeros(), tel, 1, env=ep is not None,
                        work=work is not None)
    rebase = _rebase_for(ep, work)
    if layout is None:
        return _scan_chunked(lambda c, s: step(c, s), zeros, state,
                             n_events, chunk_events, rebase=rebase)
    return _scan_chunked_slab(lambda c, s, x: step(c, s, x=x), zeros, state,
                              n_events, chunk_events, layout.n_cols,
                              paired=(ep is not None) or (work is not None),
                              rebase=rebase)


@functools.partial(
    jax.jit,
    static_argnames=("job", "spot", "kernel", "rmax", "n_events",
                     "chunk_events", "burn_in", "rng", "tel", "work"),
)
def _run_sim_jit(job, spot, kernel, rmax, n_events, chunk_events, burn_in,
                 rng, params, k_cost, key, tel=None, ep=None, work=None,
                 wk=None):
    """Single-point entry, compiled once per static signature at module scope
    (the seed re-jitted its burn-in path on every call).

    ``ep`` is traced (an env-params dict, or None — a leafless pytree, so
    the ``env=None`` program is the same jaxpr as before the axis);
    ``work``/``wk`` are the static/traced halves of the work axis, with
    the same leafless-when-off property."""
    layout = _engine_layout(job, spot, kernel) if rng == "slab" else None
    state = init_engine_state(key, job, spot, rmax, ep=ep)
    if ep is not None:
        state = (state, init_env_state(ep))
    if work is not None:
        state = (state, init_work_state(rmax))
    if burn_in:
        state, _ = run_window(job, spot, kernel, rmax, state, params, k_cost,
                              burn_in, layout=layout, tel=tel, ep=ep,
                              work=work, wk=wk)
        state = _rebase_for(ep, work)(state)
    return run_chunked(job, spot, kernel, rmax, state, params, k_cost,
                       n_events, chunk_events, layout=layout, tel=tel, ep=ep,
                       work=work, wk=wk)


def _check_rng(rng: str) -> None:
    if rng not in ("split", "slab"):
        raise ValueError(f"unknown rng {rng!r} (expected 'split'|'slab')")


def _resolve_interpret(name: str, impl: str, rng: str,
                       interpret: bool | None) -> bool:
    """The Pallas executor's mode: ``interpret=None`` is compiled Mosaic
    on a TPU backend and the Pallas interpreter on any other.  The
    compiled kernel hashes the slab stream in-kernel; the split stream's
    per-event ``jax.random.split`` has no Mosaic lowering, so that pairing
    raises instead of running some other way."""
    interpret = default_interpret() if interpret is None else interpret
    if impl == "pallas" and not interpret and rng != "slab":
        raise ValueError(
            f"{name}: the compiled Pallas kernel (interpret=False) runs "
            f"rng='slab' only, got rng={rng!r}; use rng='slab', "
            f"impl='xla', or interpret=True")
    return interpret


def _check_telemetry(telemetry) -> None:
    if telemetry is not None and not isinstance(telemetry, Telemetry):
        raise TypeError(
            f"telemetry must be a repro.obs.Telemetry or None, got "
            f"{telemetry!r}")


def _check_env(env) -> None:
    if env is not None and not isinstance(env, EnvTimeline):
        raise TypeError(
            f"env must be a repro.core.env.EnvTimeline or None, got "
            f"{env!r}")


def _env_params(env: EnvTimeline | None, n_locs: int):
    return None if env is None else env.params(n_locs)


def _check_work(work, kernel) -> None:
    if work is not None and not isinstance(work, WorkModel):
        raise TypeError(
            f"work must be a repro.core.work.WorkModel or None, got "
            f"{work!r}")
    if work is None and getattr(kernel, "safety_net", False):
        raise ValueError(
            "a safety-net kernel (CantBeLateKernel) tracks per-job slack "
            "and needs the work axis: pass work=WorkModel(...)")


def _check_run_shape(name: str, n_events: int, burn_in: int) -> None:
    """Actionable errors for the host-side run plan (every entry point)."""
    if n_events <= 0:
        raise ValueError(
            f"{name}: n_events must be a positive event count, got "
            f"{n_events}")
    if burn_in < 0:
        raise ValueError(
            f"{name}: burn_in must be >= 0 events, got {burn_in}")


def _check_loc_overrides(name: str, n_locs: int, what: str, **arrays) -> None:
    """Actionable errors for per-pool/per-region override grids: every
    given array must be a scalar (fills every loc) or have a last axis
    broadcastable to the scenario's loc count, and price/hazard/notice
    values must be non-negative and finite."""
    for field, arr in arrays.items():
        if arr is None:
            continue
        a = np.asarray(arr)
        if a.ndim > 0 and a.shape[-1] not in (1, n_locs):
            raise ValueError(
                f"{name}: {field} must be scalar or have last-axis length "
                f"{n_locs} (one per {what}), got shape {a.shape}")
        if not np.all(np.isfinite(a)):
            raise ValueError(
                f"{name}: {field} contains non-finite values")
        if np.any(a < 0):
            raise ValueError(
                f"{name}: {field} must be non-negative, got min "
                f"{a.min()}")


class NonFiniteStatsError(ValueError):
    """Raised by :func:`summarize` when a reduced statistic is NaN/inf —
    poisoned windows fail loudly at the host boundary instead of leaking
    silent NaN averages into sweeps and learners."""


def _check_finite_stats(s) -> None:
    for field in ("cost_sum", "delay_sum", "time_elapsed"):
        v = getattr(s, field)
        if not np.all(np.isfinite(v)):
            raise NonFiniteStatsError(
                f"summarize: window statistic {field!r} is non-finite "
                f"(NaN/inf) — the run diverged (bad params, non-finite "
                f"prices/hazards, or a poisoned window)")


def _flat_lane_args(params_trees, k_cost, keys):
    """Flatten a (grid × seeds) product to grid-major lanes (seed fastest).

    The Pallas executor's lane layout: params/k repeat per seed, raw seed
    keys tile per grid point — the kernel operates on materialized per-lane
    state/params tiles.  The XLA executor deliberately does NOT share this
    layout: its nested vmap with broadcast (``in_axes=None``) arguments
    compiles ~2.5× faster on CPU than any materialized-lane variant (the
    batching rules keep grid-constant operands symbolically unbatched).
    Per-lane arithmetic is the same traced event body either way; see
    EXPERIMENTS.md ("Engine kernel") for the ulp-level float caveat this
    split implies on CPU.
    """
    g, s = k_cost.shape[0], keys.shape[0]
    rep = lambda x: jnp.repeat(x, s, axis=0)
    return ([jax.tree.map(rep, t) for t in params_trees], rep(k_cost),
            jnp.tile(keys, (g, 1)))


def _unflatten_lanes(stats, g: int, s: int):
    """(lanes, windows, ...) stats leaves back to (grid, seeds, ...)."""
    return jax.tree.map(lambda x: x.reshape((g, s) + x.shape[1:]), stats)


@functools.partial(
    jax.jit,
    static_argnames=("job", "spot", "kernel", "rmax", "n_events",
                     "chunk_events", "burn_in", "rng", "tel", "work"),
)
def _run_sweep_jit(job, spot, kernel, rmax, n_events, chunk_events, burn_in,
                   rng, params, k_cost, keys, tel=None, ep=None, work=None,
                   wk=None):
    """(grid × seeds) fleet as one nested-vmap XLA program (broadcast
    ``in_axes`` — see :func:`_flat_lane_args` for why not flat lanes).
    ``ep``/``wk`` are closed over by ``one`` (grid-constant, so the nested
    vmap keeps them symbolically unbatched)."""
    layout = _engine_layout(job, spot, kernel) if rng == "slab" else None

    def one(p, kc, key):
        state = init_engine_state(key, job, spot, rmax, ep=ep)
        if ep is not None:
            state = (state, init_env_state(ep))
        if work is not None:
            state = (state, init_work_state(rmax))
        if burn_in:
            state, _ = run_window(job, spot, kernel, rmax, state, p, kc,
                                  burn_in, layout=layout, tel=tel, ep=ep,
                                  work=work, wk=wk)
            state = _rebase_for(ep, work)(state)
        _, stats = run_chunked(job, spot, kernel, rmax, state, p, kc,
                               n_events, chunk_events, layout=layout,
                               tel=tel, ep=ep, work=work, wk=wk)
        return stats

    per_seeds = jax.vmap(one, in_axes=(None, None, 0))
    return jax.vmap(per_seeds, in_axes=(0, 0, None))(params, k_cost, keys)


def _lane_slabs(state0, plan, layout: SlabLayout, compiled: bool):
    """The batched-event executors' slab stream: each lane's per-window
    slab keys, (lanes, n_windows, 2) uint32, with the row width.  Walked
    OUTSIDE the kernel from each lane's initial key; window ``w``'s rows
    are bitwise the slab the scan executor draws
    (:func:`_scan_window_slab`), hashed per event in-kernel and drawn per
    window by the reference.  A ``compiled`` kernel runs no typed-key
    PRNG, so a hook without a slab-aware ``*_u`` twin raises here."""
    if compiled and "key" in (layout.admit_mode, layout.on_preempt_mode,
                              layout.route_mode):
        raise ValueError(
            "the compiled Pallas kernel needs slab-aware kernel hooks "
            "(admit_u/admit_market_u/on_preempt_u/route_u); this kernel "
            "draws from a PRNG key — run it with impl='xla' or "
            "interpret=True")
    with jax.named_scope("repro.glue.lane_slabs"):
        keys = jax.vmap(lambda k: lane_slab_keys(k, len(plan)))(state0.key)
    return keys, layout.n_cols


def _env_lane_blocks(ep: dict, lanes: int):
    """Per-lane env inputs for the batched-event executors: the segment
    tables broadcast per lane (they become VMEM-resident param blocks,
    exactly like the PR-5 slab rides as an input block) plus each lane's
    initial :class:`EnvState` cursor."""
    ep_b = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (lanes,) + a.shape), ep)
    es0 = EnvState(
        next_boundary=jnp.broadcast_to(ep["t_end"][0], (lanes,)),
        seg=jnp.zeros((lanes,), jnp.int32))
    return ep_b, es0


@functools.partial(
    jax.jit,
    static_argnames=("job", "spot", "kernel", "rmax", "n_events",
                     "chunk_events", "burn_in", "tile", "interpret",
                     "executor", "rng", "tel", "work"),
)
def _run_sweep_pallas_jit(job, spot, kernel, rmax, n_events, chunk_events,
                          burn_in, tile, interpret, params, k_cost, keys,
                          executor="pallas", rng="split", tel=None, ep=None,
                          work=None, wk=None):
    """The (grid × seeds) fleet as ONE Pallas batched-event kernel call.

    Lanes are grid-major (seed fastest; :func:`_flat_lane_args`); per-lane
    arithmetic is the same traced :func:`_engine_event` the XLA executor
    scans.  Burn-in runs as a leading window through the same kernel and
    its stats row is dropped.  ``executor="ref"`` swaps the kernel for its
    pure-JAX scan reference on the identical lane layout — the bit-for-bit
    oracle the equivalence tests freeze the kernel against.
    """
    g, s = k_cost.shape[0], keys.shape[0]
    (params_f,), k_f, keys_f = _flat_lane_args((params,), k_cost, keys)
    params_b = {"params": params_f, "k": k_f}
    state0 = jax.vmap(
        lambda key: init_engine_state(key, job, spot, rmax, ep=ep))(keys_f)
    plan = _window_plan(n_events, chunk_events, burn_in)

    if rng == "slab":
        layout = _engine_layout(job, spot, kernel)
        slab = _lane_slabs(state0, plan, layout,
                           compiled=executor == "pallas" and not interpret)
    else:
        layout, slab = None, None
    if ep is not None:
        # slabs above walk the bare engine key ladder; only now does the
        # lane state become the (engine, env-cursor) pair
        params_b["ep"], es0 = _env_lane_blocks(ep, keys_f.shape[0])
        state0 = (state0, es0)
    if work is not None:
        # work params ride as per-lane VMEM blocks like ep; the work
        # structure wraps outermost, after any env pairing
        params_b["wk"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (keys_f.shape[0],)), wk)
        state0 = (state0, init_work_state(rmax, keys_f.shape[0]))

    if rng == "slab":
        def step(carry, stats, p, x):
            return _engine_event(job, spot, kernel, rmax, layout, carry,
                                 stats, p["params"], p["k"], x=x, tel=tel,
                                 ep=p.get("ep"), work=work, wk=p.get("wk"))
    else:
        def step(carry, stats, p):
            return _engine_event(job, spot, kernel, rmax, None, carry,
                                 stats, p["params"], p["k"], tel=tel,
                                 ep=p.get("ep"), work=work, wk=p.get("wk"))

    zeros = _with_zeros(WindowStats.zeros(), tel, 1, env=ep is not None,
                        work=work is not None)
    epilogue = _rebase_for(ep, work)
    if executor == "ref":
        _, stats = batched_event_windows_ref(
            step, state0, params_b, zeros, plan, slab=slab,
            epilogue=epilogue)
    else:
        _, stats = batched_events(
            step, state0, params_b, zeros, plan, slab=slab,
            tile=tile, interpret=interpret, epilogue=epilogue)
    if burn_in:
        stats = jax.tree.map(lambda x: x[:, 1:], stats)
    return _unflatten_lanes(stats, g, s)


def _check_shard(name: str, shard: str, mesh) -> None:
    """Actionable errors for the ``shard=`` axis (every sweep entry point)."""
    if shard not in ("none", "lanes"):
        raise ValueError(
            f"{name}: unknown shard {shard!r} (expected 'none'|'lanes')")
    if mesh is not None:
        if shard == "none":
            raise ValueError(
                f"{name}: mesh= requires shard='lanes' (shard='none' runs "
                f"unsharded)")
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"{name}: lane sharding needs a 1-D mesh, got axes "
                f"{mesh.axis_names}")


def _pad_count(lanes: int, mesh) -> int:
    """Lanes to add so the flat lane axis divides the mesh evenly."""
    return -lanes % mesh.size


def _sweep_lanes(job, spot, kernel, rmax, n_events, chunk_events, burn_in,
                 tile, interpret, params_f, k_f, keys_f, *, executor, rng,
                 tel=None, ep=None, work=None, wk=None):
    """One shard's worth of flat lanes through the requested executor.

    The per-shard body of the ``shard="lanes"`` dispatch: arguments are
    already flat lane-leading (grid-major, seed fastest — the
    :func:`_flat_lane_args` layout; ``keys_f`` are raw uint32 key words),
    and the returned stats leaves are ``(lanes, windows, ...)``.  The
    ``"pallas"``/``"ref"`` branches mirror :func:`_run_sweep_pallas_jit`'s
    body op-for-op, so per-lane trajectories are bitwise the unsharded
    ones.  The ``"xla"`` branch runs the same per-lane program as
    :func:`_run_sweep_jit`'s ``one`` but under a single flat vmap —
    materialized lanes instead of broadcast nesting, which keeps integer
    stats bitwise and float sums within ~ulp of the unsharded nested-vmap
    program (the PR-3 layout caveat; see :func:`_flat_lane_args`).
    """
    layout = _engine_layout(job, spot, kernel) if rng == "slab" else None
    if executor == "xla":
        def one(p, kc, key):
            state = init_engine_state(key, job, spot, rmax, ep=ep)
            if ep is not None:
                state = (state, init_env_state(ep))
            if work is not None:
                state = (state, init_work_state(rmax))
            if burn_in:
                state, _ = run_window(job, spot, kernel, rmax, state, p, kc,
                                      burn_in, layout=layout, tel=tel, ep=ep,
                                      work=work, wk=wk)
                state = _rebase_for(ep, work)(state)
            _, stats = run_chunked(job, spot, kernel, rmax, state, p, kc,
                                   n_events, chunk_events, layout=layout,
                                   tel=tel, ep=ep, work=work, wk=wk)
            return stats

        return jax.vmap(one)(params_f, k_f, keys_f)

    params_b = {"params": params_f, "k": k_f}
    state0 = jax.vmap(
        lambda key: init_engine_state(key, job, spot, rmax, ep=ep))(keys_f)
    plan = _window_plan(n_events, chunk_events, burn_in)
    slab = None if layout is None else _lane_slabs(
        state0, plan, layout, compiled=executor == "pallas" and not interpret)
    if ep is not None:
        params_b["ep"], es0 = _env_lane_blocks(ep, keys_f.shape[0])
        state0 = (state0, es0)
    if work is not None:
        params_b["wk"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (keys_f.shape[0],)), wk)
        state0 = (state0, init_work_state(rmax, keys_f.shape[0]))

    if layout is not None:
        def step(carry, stats, p, x):
            return _engine_event(job, spot, kernel, rmax, layout, carry,
                                 stats, p["params"], p["k"], x=x, tel=tel,
                                 ep=p.get("ep"), work=work, wk=p.get("wk"))
    else:
        def step(carry, stats, p):
            return _engine_event(job, spot, kernel, rmax, None, carry,
                                 stats, p["params"], p["k"], tel=tel,
                                 ep=p.get("ep"), work=work, wk=p.get("wk"))

    zeros = _with_zeros(WindowStats.zeros(), tel, 1, env=ep is not None,
                        work=work is not None)
    epilogue = _rebase_for(ep, work)
    if executor == "ref":
        _, stats = batched_event_windows_ref(
            step, state0, params_b, zeros, plan, slab=slab, epilogue=epilogue)
    else:
        _, stats = batched_events(
            step, state0, params_b, zeros, plan, slab=slab, tile=tile,
            interpret=interpret, epilogue=epilogue)
    if burn_in:
        stats = jax.tree.map(lambda x: x[:, 1:], stats)
    return stats


@functools.partial(
    jax.jit,
    static_argnames=("job", "spot", "kernel", "rmax", "n_events",
                     "chunk_events", "burn_in", "tile", "interpret", "mesh",
                     "executor", "rng", "tel", "work"),
)
def _run_sweep_sharded_jit(job, spot, kernel, rmax, n_events, chunk_events,
                           burn_in, tile, interpret, mesh, params, k_cost,
                           keys, executor="xla", rng="split", tel=None,
                           ep=None, work=None, wk=None):
    """The (grid × seeds) fleet lane-partitioned across a 1-D device mesh.

    Flatten to grid-major lanes, pad to a mesh-size multiple with copies
    of lane 0 (:func:`repro.distributed.sharding.pad_lanes`), run
    :func:`_sweep_lanes` per shard under ``shard_map`` (env tables ride
    replicated), slice the pad lanes off, and unflatten.  No cross-lane
    communication exists in the event loop — lane keys are independent in
    both rng streams — so each shard's trajectories are the unsharded
    ones by construction; the host-side summaries then reduce int32
    windows with integer addition (no float reduction-order hazard on the
    ledger's exact set).
    """
    g, s = k_cost.shape[0], keys.shape[0]
    (params_f,), k_f, keys_f = _flat_lane_args((params,), k_cost, keys)
    lanes = g * s
    params_f, k_f, keys_f = pad_lanes((params_f, k_f, keys_f),
                                      _pad_count(lanes, mesh))
    spec, rspec = lane_spec(mesh), jax.sharding.PartitionSpec()

    def local(pf, kf, keysf, ep_, wk_):
        return _sweep_lanes(job, spot, kernel, rmax, n_events, chunk_events,
                            burn_in, tile, interpret, pf, kf, keysf,
                            executor=executor, rng=rng, tel=tel, ep=ep_,
                            work=work, wk=wk_)

    stats = shard_map_1d(local, mesh=mesh,
                         in_specs=(spec, spec, spec, rspec, rspec),
                         out_specs=spec)(params_f, k_f, keys_f, ep, wk)
    if lanes != keys_f.shape[0]:
        stats = jax.tree.map(lambda x: x[:lanes], stats)
    return _unflatten_lanes(stats, g, s)


#: Statistics that count events (int32 window accumulators and their
#: per-pool variants).  Event *decisions* never differ between executors,
#: so these are bitwise identical across impl="xla"/"pallas"/"ref" on any
#: config — the exact-comparison set used by the equivalence tests,
#: benches, and examples (float sums get the ~ulp contract instead; see
#: the module docstring).
INT_STATS = ("jobs_arrived", "jobs_completed", "spot_served", "ondemand",
             "preemptions", "resumed", "pool_served", "pool_spot_arrivals",
             "pool_preempted", "routed_home", "region_served",
             "region_spot_arrivals", "region_preempted", "region_jobs",
             "region_routed")


def _merge_telemetry(out: dict, telemetry: Telemetry, tstats,
                     time_elapsed) -> dict:
    """Append the telemetry summary (new fields only — base keys are never
    touched) plus the per-window durations the trace exporter needs to
    place each window's ring on a global clock."""
    tout = summarize_telemetry(telemetry, tstats)
    if "trace" in tout:
        tout["trace"]["time_windows"] = np.asarray(time_elapsed, np.float64)
    out.update(tout)
    return out


def summarize(stats: WindowStats, telemetry: Telemetry | None = None,
              env=None, work=None) -> dict:
    """Reduce chunked (…, n_chunks) sums in float64; derive long-run stats.

    Leading batch axes (grid, seeds) pass through: every value in the
    returned dict is a numpy array of the batch shape (0-d for a single run).
    With ``telemetry``, ``stats`` is the engine's ``(base, telemetry)``
    pair and the dict gains the :func:`repro.obs.summarize_telemetry`
    fields (P50/P99 wait, event counters, …) — base keys unchanged.
    With ``env`` (truthy), ``stats`` is additionally wrapped in an
    outermost ``(stats, EnvWindowStats)`` pair and the dict gains the
    :func:`repro.obs.summarize_env` shock/degradation counters.
    With ``work`` (truthy), the outermost pair is
    ``(stats, SurvivalWindowStats)`` and the dict gains the
    :func:`repro.obs.summarize_survival` job-level ledger.
    Raises :class:`NonFiniteStatsError` when a reduced base statistic is
    NaN/inf (silent poisoned stats fail loudly at the host boundary).
    """
    wstats = None
    if work is not None:
        stats, wstats = stats
    estats = None
    if env is not None:
        stats, estats = stats
    tstats = None
    if telemetry is not None:
        stats, tstats = stats
    s = jax.tree.map(lambda x: np.asarray(x, np.float64).sum(axis=-1), stats)
    _check_finite_stats(s)
    completed = np.maximum(s.jobs_completed, 1.0)
    arrived = np.maximum(s.jobs_arrived, 1.0)
    time = np.maximum(s.time_elapsed, 1e-12)
    spot_arr = np.maximum(s.spot_arrivals, 1.0)
    out = {
        "jobs_arrived": s.jobs_arrived,
        "jobs_completed": s.jobs_completed,
        "spot_served": s.spot_served,
        "ondemand": s.ondemand,
        "avg_cost": s.cost_sum / completed,
        "avg_delay": s.delay_sum / completed,
        "time": s.time_elapsed,
        "pi0_time": s.empty_time / time,
        "pi0_spot": s.spot_found_empty / spot_arr,
        "spot_utilization": (s.spot_arrivals - s.spot_found_empty) / spot_arr,
        "arrival_rate": arrived / time,
    }
    if telemetry is not None:
        out = _merge_telemetry(out, telemetry, tstats, stats.time_elapsed)
    if estats is not None:
        out.update(summarize_env(estats))
    if wstats is not None:
        out.update(summarize_survival(wstats))
    return out


def _scalar_or_array(v):
    """Single-run host conversion: 0-d → float (the frozen sim contract),
    arrays stay arrays (per-pool/per-region/histogram fields), the trace
    dict passes through."""
    if isinstance(v, dict):
        return v
    return float(v) if np.ndim(v) == 0 else np.asarray(v)


def _reshape_sweep(out: dict, grid_shape: tuple, n_seeds: int) -> dict:
    """Reshape flat ``(grid_points, n_seeds, ...)`` summary values back to
    ``grid_shape + (n_seeds,) + trailing`` — generic over scalar,
    per-pool/per-region, histogram, and (nested) trace-dict fields."""
    def _r(v):
        v = np.asarray(v)
        return v.reshape(grid_shape + (n_seeds,) + v.shape[2:])

    return {name: ({key: _r(x) for key, x in v.items()}
                   if isinstance(v, dict) else _r(v))
            for name, v in out.items()}


def run_sim(
    job: ArrivalProcess,
    spot: ArrivalProcess,
    kernel: PolicyKernel,
    params=None,
    *,
    k: float = 10.0,
    n_events: int,
    key: jax.Array,
    rmax: int = 64,
    burn_in: int = 0,
    chunk_events: int | None = DEFAULT_CHUNK_EVENTS,
    impl: str = "xla",
    rng: str = "split",
    tile: int = 256,
    interpret: bool | None = None,
    telemetry: Telemetry | None = None,
    env: EnvTimeline | None = None,
    work: WorkModel | None = None,
) -> dict:
    """Run one policy at one parameter point; return long-run scalar stats.

    ``chunk_events`` defaults to :data:`DEFAULT_CHUNK_EVENTS` like the sweep
    entry points (chunks clamp to ``n_events``, so horizons within one chunk
    still accumulate in a single float32 window — the seed simulators'
    bit-for-bit behaviour); ``None`` forces a single window at any horizon.
    ``impl="pallas"`` runs the horizon as a one-lane batched-event kernel
    call — bit-for-bit the ``"ref"`` scan oracle; see :func:`run_sweep`
    and the module docstring for the cross-executor equality contract.
    ``rng="slab"`` selects the fast slab PRNG stream (module docstring,
    "Randomness").  ``telemetry`` (a :class:`repro.obs.Telemetry`) adds
    streaming P50/P99 wait/cost sketches, event counters, and optionally
    an event trace to the returned dict (module docstring, "Telemetry").
    ``env`` (a :class:`repro.core.env.EnvTimeline`) runs the horizon
    through a piecewise-constant environment — price/hazard/availability
    segments, storms, blackouts — and adds the shock counters to the
    returned dict (module docstring of :mod:`repro.core.env`).
    ``work`` (a :class:`repro.core.work.WorkModel`) gives every job a
    work structure — multi-unit service, restart overhead, checkpoints,
    deadlines — and adds the survival ledger to the returned dict
    (module docstring of :mod:`repro.core.work`).
    """
    with EntrySpan(f"repro.run_sim[{impl}]") as call:
        params = {} if params is None else params
        _check_rng(rng)
        _check_telemetry(telemetry)
        _check_env(env)
        _check_work(work, kernel)
        _check_run_shape("run_sim", n_events, burn_in)
        interp = _resolve_interpret("run_sim", impl, rng, interpret)
        ep = _env_params(env, 1)
        wk = None if work is None else work.params()
        chunk = (n_events if chunk_events is None
                 else min(chunk_events, n_events))
        call.phase("dispatch")
        if impl in ("pallas", "ref"):
            stats = _run_sweep_pallas_jit(
                job, spot, kernel, rmax, n_events, chunk, burn_in, tile,
                interp,
                jax.tree.map(lambda x: jnp.asarray(x)[None], params),
                jnp.float32(k)[None], _raw_keys(key)[None], executor=impl,
                rng=rng, tel=telemetry, ep=ep, work=work, wk=wk)
            stats = jax.tree.map(lambda x: x[0, 0], stats)
        elif impl == "xla":
            _, stats = _run_sim_jit(job, spot, kernel, rmax, n_events, chunk,
                                    burn_in, rng, params, jnp.float32(k),
                                    key, tel=telemetry, ep=ep, work=work,
                                    wk=wk)
        else:
            raise ValueError(
                f"unknown impl {impl!r} (expected 'xla'|'pallas'|'ref')")
        stats = call.to_host(stats)
        return {name: _scalar_or_array(v)
                for name, v in summarize(stats, telemetry, env=env,
                                         work=work).items()}


def run_sweep(
    job: ArrivalProcess,
    spot: ArrivalProcess,
    kernel: PolicyKernel,
    params=None,
    *,
    k: float | np.ndarray | jax.Array = 10.0,
    n_events: int,
    key: jax.Array,
    n_seeds: int = 1,
    rmax: int = 64,
    burn_in: int = 0,
    chunk_events: int | None = DEFAULT_CHUNK_EVENTS,
    impl: str = "xla",
    rng: str = "split",
    tile: int = 256,
    interpret: bool | None = None,
    telemetry: Telemetry | None = None,
    env: EnvTimeline | None = None,
    work: WorkModel | None = None,
    shard: str = "none",
    mesh=None,
) -> dict:
    """Run a whole policy grid × seed fleet as ONE jitted call.

    ``params`` is a pytree whose leaves, together with ``k``, broadcast to a
    common grid shape (e.g. ``{"r": jnp.linspace(0, 4, 32)}``, or a 2-D
    meshgrid over ``r`` × ``k``).  Seeds use common random numbers across the
    grid (same ``n_seeds`` subkeys at every point), which cancels sampling
    noise out of cross-grid comparisons.

    ``impl`` selects the executor: ``"xla"`` is the nested-vmap
    ``lax.scan`` program; ``"pallas"`` runs the fleet through the batched
    -event kernel (:mod:`repro.kernels.sweep`) — engine state resident in
    VMEM as lane-last (rmax, tile) blocks for a whole float32 window of
    events; ``"ref"`` is the kernel's pure-JAX scan reference (the
    bit-for-bit oracle; see the module docstring for the exact
    cross-executor equality contract).  ``tile`` is lanes per kernel
    instance (compiled: a multiple of 128, or every lane);
    ``interpret=None`` auto-selects compiled Mosaic on TPU and the Pallas
    interpreter elsewhere; ``interpret=False`` compiles or fails, and
    runs ``rng="slab"`` only.  ``rng="slab"`` selects the
    fast slab PRNG stream (module docstring, "Randomness") — recommended
    for new sweeps; the default ``"split"`` is the frozen seed-compatible
    stream.

    ``shard="lanes"`` partitions the flattened (grid × seeds) lane axis
    across a 1-D device mesh with ``shard_map`` (``mesh`` defaults to
    :func:`repro.distributed.sharding.lane_mesh` over every local device);
    uneven lane counts pad with copies of lane 0 and mask the pad off.
    Lane trajectories are unchanged by construction — integer stats and
    telemetry histograms match the unsharded run bitwise, float sums to
    ~ulp (the sharding-equivalence ledger, tests/test_fleet.py; see
    docs/scaling.md).

    Returns :func:`summarize`'s dict with every value shaped
    ``grid_shape + (n_seeds,)``.
    """
    with EntrySpan(f"repro.run_sweep[{impl}]") as call:
        params = {} if params is None else params
        _check_rng(rng)
        _check_telemetry(telemetry)
        _check_env(env)
        _check_work(work, kernel)
        _check_shard("run_sweep", shard, mesh)
        _check_run_shape("run_sweep", n_events, burn_in)
        interp = _resolve_interpret("run_sweep", impl, rng, interpret)
        ep = _env_params(env, 1)
        wk = None if work is None else work.params()
        params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
        k = jnp.asarray(k, jnp.float32)
        grid_shape = jnp.broadcast_shapes(
            k.shape, *(x.shape for x in jax.tree.leaves(params))
        )
        flat = lambda x: jnp.broadcast_to(x, grid_shape).reshape(-1)
        params_flat = jax.tree.map(flat, params)
        k_flat = flat(k)
        keys = jax.random.split(key, n_seeds)
        chunk = (n_events if chunk_events is None
                 else min(chunk_events, n_events))
        call.phase("dispatch")
        if shard == "lanes":
            if impl not in ("xla", "pallas", "ref"):
                raise ValueError(
                    f"unknown impl {impl!r} (expected 'xla'|'pallas'|'ref')")
            stats = _run_sweep_sharded_jit(
                job, spot, kernel, rmax, n_events, chunk, burn_in, tile,
                interp,
                lane_mesh() if mesh is None else mesh, params_flat, k_flat,
                _raw_keys(keys), executor=impl, rng=rng, tel=telemetry,
                ep=ep, work=work, wk=wk)
        elif impl in ("pallas", "ref"):
            stats = _run_sweep_pallas_jit(
                job, spot, kernel, rmax, n_events, chunk, burn_in, tile,
                interp,
                params_flat, k_flat, _raw_keys(keys), executor=impl,
                rng=rng, tel=telemetry, ep=ep, work=work, wk=wk)
        elif impl == "xla":
            stats = _run_sweep_jit(job, spot, kernel, rmax, n_events, chunk,
                                   burn_in, rng, params_flat, k_flat, keys,
                                   tel=telemetry, ep=ep, work=work, wk=wk)
        else:
            raise ValueError(
                f"unknown impl {impl!r} (expected 'xla'|'pallas'|'ref')")
        stats = call.to_host(stats)
        # values shaped (grid_points, n_seeds)
        out = summarize(stats, telemetry, env=env, work=work)
        return _reshape_sweep(out, grid_shape, n_seeds)


# ===========================================================================
# SpotMarket: P heterogeneous pools + preemption-with-notice
# ===========================================================================
#
# The market event loop is the PR-1 loop with the scalar ``next_spot`` clock
# widened to per-pool vectors ``next_spot``/``next_preempt`` (see
# repro.core.market for the descriptors and model semantics).  Event-time
# ties resolve spot > preempt > deadline > job; ties *between* pools resolve
# by position (argmin), measure-zero for continuous samplers.
#
# With a degenerate market (1 pool, zero hazard, unit price) every branch
# below reduces bitwise to the PR-1 expressions: the preemption machinery is
# statically removed (4-way key split, untouched INF preempt clock), the
# single-pool min/argmin are exact identities, and the extra stat terms add
# literal +0.0 to non-negative float32 sums.  tests/test_core_market.py
# freezes that contract against run_sim/run_sweep.


class MarketWindowStats(NamedTuple):
    """Per-window accumulators for the market loop.

    The first ten fields mirror :class:`WindowStats` exactly (same order,
    same accumulation semantics); the tail adds preemption and per-pool
    counters.  Under preemption, completions count *legs* — a checkpointed
    job contributes one completed leg at revocation and another when it
    finally finishes, matching the host orchestrator's accounting.
    """

    jobs_arrived: jax.Array
    jobs_completed: jax.Array
    spot_served: jax.Array
    ondemand: jax.Array
    cost_sum: jax.Array
    delay_sum: jax.Array
    time_elapsed: jax.Array
    empty_time: jax.Array
    spot_arrivals: jax.Array
    spot_found_empty: jax.Array
    resumed: jax.Array  # i32: preempted legs that checkpointed + re-queued
    spot_cost: jax.Array  # f32: cost paid to spot pools (incl. partial legs)
    pool_served: jax.Array  # (P,) i32 completions per pool
    pool_spot_arrivals: jax.Array  # (P,) i32 slot arrivals per pool
    pool_preempted: jax.Array  # (P,) i32 preemption hits per pool

    @staticmethod
    def zeros(n_pools: int) -> "MarketWindowStats":
        z = jnp.zeros((), jnp.float32)
        zi = jnp.zeros((), jnp.int32)
        zp = jnp.zeros((n_pools,), jnp.int32)
        return MarketWindowStats(zi, zi, zi, zi, z, z, z, z, zi, zi,
                                 zi, z, zp, zp, zp)


_POOL_FIELDS = frozenset({"pool_served", "pool_spot_arrivals",
                          "pool_preempted"})


class MarketState(NamedTuple):
    key: jax.Array
    next_job: jax.Array  # time until next job arrival
    next_spot: jax.Array  # (P,) per-pool spot-slot clocks
    next_preempt: jax.Array  # (P,) per-pool preemption clocks (INF = never)
    ages: jax.Array  # (rmax,)
    budgets: jax.Array  # (rmax,)
    occ: jax.Array  # (rmax,) bool
    pool: jax.Array  # (rmax,) int32 pool tag of each queued job
    order: jax.Array  # (rmax,) int32 join sequence number
    next_seq: jax.Array
    qlen: jax.Array


def _market_tags(market: SpotMarket) -> tuple:
    return tuple(p.tag for p in market.pools)


def _sample_spot_clocks(market: SpotMarket, k_spot: jax.Array,
                        mp: dict) -> jax.Array:
    """Per-pool spot clock refresh (split stream): tag-folded keys via the
    shared :func:`repro.core.clocks.sample_clock_vector` plumbing — the
    1-pool market uses ``k_spot`` directly (the PR-1 key layout), so the
    degenerate engine is bit-for-bit the PR-1 engine."""
    return sample_clock_vector(tuple(p.arrival for p in market.pools),
                               _market_tags(market), k_spot,
                               mp["spot_scale"])


def _slab_spot_clocks(procs: tuple, u: jax.Array,
                      scale: jax.Array) -> jax.Array:
    """Slab-stream clock-vector refresh: every process transforms the SAME
    shared uniforms (only the firing entry's sample is ever consumed, so
    sharing the columns is distributionally exact) — zero key arithmetic,
    O(P) cheap transforms."""
    return jnp.stack([p.sample_u(u) for p in procs]) * scale


def init_market_state(key: jax.Array, job: ArrivalProcess,
                      market: SpotMarket, rmax: int, mp: dict,
                      preempt_on: bool,
                      scalar_preempt: bool = False,
                      ep: dict | None = None) -> MarketState:
    """``scalar_preempt`` (the ``rng="slab"`` representation) carries ONE
    superposed preemption clock instead of the (P,) vector: the min of the
    per-pool init draws — exactly ``Exp(Σ h_p)``, the superposition law.
    ``ep`` places the initial clocks under segment 0's effective hazard
    and availability (exact ×1.0 no-ops on a constant timeline)."""
    kj, ks, kc = jax.random.split(key, 3)
    n = market.n_pools
    hazard0 = (mp["hazard"] if ep is None
               else mp["hazard"] * ep["hazard"][0])
    if preempt_on:
        next_preempt = sample_hazard_clocks(
            _market_tags(market), jax.random.fold_in(ks, 2**31 - 1),
            hazard0)
        if scalar_preempt:
            next_preempt = jnp.min(next_preempt, keepdims=True)
    else:
        next_preempt = jnp.full((1 if scalar_preempt else n,), INF,
                                jnp.float32)
    next_job = job.sample(kj)
    next_spot = _sample_spot_clocks(market, ks, mp)
    if ep is not None:
        next_spot = next_spot * inv_avail(ep["avail"][0])
    return MarketState(
        key=kc,
        next_job=next_job,
        next_spot=next_spot,
        next_preempt=next_preempt,
        ages=jnp.zeros((rmax,), jnp.float32),
        budgets=jnp.full((rmax,), INF, jnp.float32),
        occ=jnp.zeros((rmax,), jnp.bool_),
        pool=jnp.zeros((rmax,), jnp.int32),
        order=jnp.zeros((rmax,), jnp.int32),
        next_seq=jnp.zeros((), jnp.int32),
        qlen=jnp.zeros((), jnp.int32),
    )


def _kernel_admit(kernel, params, qlen, pool_state, key):
    """Route market-aware kernels to admit_market; legacy kernels to pool 0
    with the PR-1 key layout (degenerate bit-for-bit)."""
    if hasattr(kernel, "admit_market"):
        admit, budget, pool = kernel.admit_market(params, qlen, pool_state,
                                                  key)
        return admit, budget, jnp.asarray(pool, jnp.int32)
    admit, budget = kernel.admit(params, qlen, key)
    return admit, budget, jnp.zeros((), jnp.int32)


def _kernel_admit_slab(kernel, params, qlen, pool_state, layout: SlabLayout,
                       x):
    """Slab-stream twin of :func:`_kernel_admit`: slab-aware kernels own
    their uniform columns; legacy hooks get a synthesized key."""
    if layout.market_admit:
        if layout.admit_mode == "u":
            admit, budget, pool = kernel.admit_market_u(
                params, qlen, pool_state, layout.uniforms(x, layout.admit))
        else:
            admit, budget, pool = kernel.admit_market(
                params, qlen, pool_state,
                synth_key(layout.bits(x, layout.admit)))
        return admit, budget, jnp.asarray(pool, jnp.int32)
    admit, budget = _admit_slab(kernel, params, qlen, layout, x)
    return admit, budget, jnp.zeros((), jnp.int32)


def _kernel_on_preempt(kernel, params, age, notice, qlen, key):
    if hasattr(kernel, "on_preempt"):
        return kernel.on_preempt(params, age, notice, qlen, key)
    return jnp.zeros((), jnp.bool_)  # legacy kernels defect on revocation


def _kernel_on_preempt_slab(kernel, params, age, notice, qlen,
                            layout: SlabLayout, x):
    if layout.on_preempt_mode == "u":
        return kernel.on_preempt_u(params, age, notice, qlen,
                                   layout.uniforms(x, layout.on_preempt))
    if layout.on_preempt_mode == "key":
        return kernel.on_preempt(params, age, notice, qlen,
                                 synth_key(layout.bits(x, layout.on_preempt)))
    return jnp.zeros((), jnp.bool_)  # legacy kernels defect on revocation


def _market_event(job: ArrivalProcess, market: SpotMarket, kernel, rmax: int,
                  preempt_on: bool, layout: SlabLayout | None,
                  carry: MarketState, stats: MarketWindowStats, params,
                  mp: dict, k_cost: jax.Array,
                  x: jax.Array | None = None, tel: Telemetry | None = None,
                  ep: dict | None = None, work: WorkModel | None = None,
                  wk: dict | None = None
                  ) -> tuple[MarketState, MarketWindowStats]:
    """One merged event: job arrival / pool spot slot / pool preemption /
    wait deadline.  Same dense one-hot-select style as :func:`_engine_event`
    (see the note there on scatter vs select under vmap).

    ``layout=None`` is the frozen split stream; with a :class:`SlabLayout`
    the body consumes slab row ``x`` instead — and the (P,) preemption
    clock vector is ONE superposed clock at total hazard plus a thinning
    pick of the firing pool (exact; see :mod:`repro.core.clocks`).
    ``tel`` appends the telemetry fold exactly as in :func:`_engine_event`
    (base expressions untouched); the event locus is the firing pool.
    ``ep`` threads the environment-timeline axis exactly as in
    :func:`_engine_event`, here with per-pool multiplier rows: effective
    price/hazard = base × segment row, spot supply scaled by per-pool
    availability (0 = blackout, clocks inflated finite), and the kernel's
    :class:`PoolState` sees the *effective* market — a zero ``rate`` entry
    is the blackout signal failover kernels key on.
    ``work``/``wk`` thread the work axis exactly as in
    :func:`_engine_event`; here preemption makes it bite — a resumed job
    rolls back to its checkpoint and owes the restart overhead, and the
    ledger prices every rollback.
    """
    n_pools = market.n_pools
    if work is not None:
        carry, wk_c = carry
        stats, wstats = stats
    if ep is not None:
        carry, env_c = carry
        stats, estats = stats
        seg = env_c.seg
        avail_row = env_row(ep["avail"], seg)
        eff_hazard = mp["hazard"] * env_row(ep["hazard"], seg)
        eff_price = mp["price"] * env_row(ep["price"], seg)
    else:
        eff_hazard = mp["hazard"]
        eff_price = mp["price"]
    if tel is not None:
        stats, tstats = stats
    if layout is None:
        key, k_job, k_spot, k_pol, k_pre, _ = split_event_keys(
            carry.key, preempt_on)
    else:
        key = carry.key
    iota = jax.lax.iota(jnp.int32, rmax)
    iota_p = jax.lax.iota(jnp.int32, n_pools)

    budgets_masked = jnp.where(carry.occ, carry.budgets, INF)
    if work is not None and getattr(kernel, "safety_net", False):
        # can't-be-late watchdog (see _engine_event): the panic clock
        # joins the budget race, so a panic is a forced-early defection
        # to on-demand through the existing deadline machinery
        buf = np.float32(getattr(kernel, "slack_buffer", 0.0))
        rem_tot_all = wk_c.oh + jnp.maximum(wk["total_work"] - wk_c.prog,
                                            0.0)
        panic_at = jnp.maximum(
            deadline_slack(wk["deadline"], wk_c.life, rem_tot_all,
                           wk["od_time"], buf), 0.0)
        panic_at = jnp.where(carry.occ, panic_at, INF)
        panic_armed = panic_at < budgets_masked
        budgets_masked = jnp.minimum(budgets_masked, panic_at)
    else:
        panic_armed = None
    deadline = jnp.min(budgets_masked)
    defect_slot = argmin_first(budgets_masked)

    min_spot = jnp.min(carry.next_spot)
    spot_pool = argmin_first(carry.next_spot).astype(jnp.int32)
    if preempt_on:
        if layout is None:
            min_pre = jnp.min(carry.next_preempt)
            pre_pool = argmin_first(carry.next_preempt).astype(jnp.int32)
        else:
            min_pre = carry.next_preempt[0]
            pre_pool = thinning_pick(eff_hazard,
                                     layout.uniforms(x, layout.preempt)[1])
        dt = jnp.minimum(jnp.minimum(carry.next_job, min_spot),
                         jnp.minimum(deadline, min_pre))
        is_spot = min_spot <= jnp.minimum(carry.next_job,
                                          jnp.minimum(deadline, min_pre))
        is_pre = (~is_spot) & (min_pre <= jnp.minimum(carry.next_job,
                                                      deadline))
        is_deadline = (~is_spot) & (~is_pre) & (deadline <= carry.next_job)
        is_job = (~is_spot) & (~is_pre) & (~is_deadline)
    else:
        pre_pool = jnp.zeros((), jnp.int32)
        dt = jnp.minimum(jnp.minimum(carry.next_job, min_spot), deadline)
        is_spot = min_spot <= jnp.minimum(carry.next_job, deadline)
        is_pre = jnp.zeros((), jnp.bool_)
        is_deadline = (~is_spot) & (deadline <= carry.next_job)
        is_job = (~is_spot) & (~is_deadline)
    if ep is not None:
        # boundary-as-event (see _engine_event): the crossing outranks
        # every queue clock, so dt never spans segments
        is_boundary = env_c.next_boundary <= dt
        dt = jnp.minimum(dt, env_c.next_boundary)
        not_b = ~is_boundary
        is_spot = is_spot & not_b
        is_pre = is_pre & not_b
        is_deadline = is_deadline & not_b
        is_job = is_job & not_b

    ages = carry.ages + dt
    budgets = jnp.where(carry.occ, carry.budgets - dt, INF)

    if ep is not None and getattr(kernel, "drain_dead", False):
        # PanicKernel drain: re-tag jobs stranded on a blacked-out pool to
        # the cheapest alive pool (the host orchestrator's re-queue step,
        # on device) so they stop pinning qlen — the PR-7 stranded-job
        # caveat.  Availability is recomputed here instead of hoisting the
        # `rates` expression below, so the drain-off program keeps its
        # original op order (CSE merges the duplicate).
        alive_p = (mp["rate"] / mp["spot_scale"]) * avail_row > 0
        cheapest = argmin_first(
            jnp.where(alive_p, eff_price, INF)).astype(jnp.int32)
        alive_slot = jnp.sum(
            jnp.where(carry.pool[:, None] == iota_p[None, :],
                      alive_p[None, :].astype(jnp.int32), 0), axis=1) > 0
        retag = carry.occ & (~alive_slot) & jnp.any(alive_p)
        carry = carry._replace(pool=jnp.where(retag, cheapest, carry.pool))

    # ---- job arrival: ask the policy kernel (admission + pool choice) ----
    qlen_pool = jnp.sum(
        (carry.occ[:, None] & (carry.pool[:, None] == iota_p[None, :]))
        .astype(jnp.int32), axis=0)
    rates = mp["rate"] / mp["spot_scale"]
    if ep is not None:
        rates = rates * avail_row  # 0 on blacked-out pools: the signal
    pool_state = PoolState(price=eff_price, hazard=eff_hazard,
                           notice=mp["notice"], rate=rates,
                           qlen_pool=qlen_pool)
    if layout is None:
        admit_raw, budget, pool_choice = _kernel_admit(kernel, params,
                                                       carry.qlen,
                                                       pool_state, k_pol)
    else:
        admit_raw, budget, pool_choice = _kernel_admit_slab(
            kernel, params, carry.qlen, pool_state, layout, x)
    admit = is_job & admit_raw & (carry.qlen < rmax)
    od_now = is_job & (~admit)
    join_slot = argmin_first(carry.occ.astype(jnp.int32))

    # ---- pool spot slot: serve the FIFO-oldest job tagged to that pool ----
    eligible_s = carry.occ & (carry.pool == spot_pool)
    serve_slot = argmin_first(jnp.where(eligible_s, carry.order, _ORDER_MAX))
    has_elig = jnp.any(eligible_s)
    served = is_spot & has_elig
    wait_served = jnp.sum(jnp.where(iota == serve_slot, ages, 0.0))
    price_s = eff_price[spot_pool]

    if work is not None:
        # one unit of service: overhead debt first, spill into progress;
        # final only when the remaining total clears (see _engine_event)
        serve_vec = served & (iota == serve_slot)
        rem_tot = wk_c.oh + (wk["total_work"] - wk_c.prog)
        rem_serve = jnp.sum(jnp.where(iota == serve_slot, rem_tot, 0.0))
        oh_new = jnp.where(serve_vec, jnp.maximum(wk_c.oh - 1.0, 0.0),
                           wk_c.oh)
        spill = jnp.maximum(1.0 - wk_c.oh, 0.0)
        prog_new = jnp.where(
            serve_vec, jnp.minimum(wk_c.prog + spill, wk["total_work"]),
            wk_c.prog)
        done_inc = jnp.sum(jnp.where(serve_vec, prog_new - wk_c.prog, 0.0))
        if work.ckpt == "periodic":
            take_vec = (serve_vec & (rem_tot > 1.0)
                        & (prog_new - wk_c.ckpt >= wk["ckpt_period"]))
            ckpt_new = jnp.where(take_vec, prog_new, wk_c.ckpt)
            oh_new = oh_new + jnp.where(take_vec, wk["ckpt_cost"], 0.0)
            ckpt_taken = jnp.any(take_vec)
        else:
            ckpt_new = wk_c.ckpt
            ckpt_taken = jnp.zeros((), jnp.bool_)
        complete_serve = served & (rem_serve <= 1.0)
    else:
        complete_serve = served

    # ---- pool preemption: revoke the FIFO-oldest job on that pool ----
    if preempt_on:
        eligible_p = carry.occ & (carry.pool == pre_pool)
        pre_slot = argmin_first(jnp.where(eligible_p, carry.order, _ORDER_MAX))
        pre_hit = is_pre & jnp.any(eligible_p)
        age_pre = jnp.sum(jnp.where(iota == pre_slot, ages, 0.0))
        # re-admission sees the queue WITHOUT the revoked job (the host
        # orchestrator pops it before consulting the admission law)
        qlen_wo = jnp.maximum(carry.qlen - 1, 0)
        if layout is None:
            resume_raw = _kernel_on_preempt(kernel, params, age_pre,
                                            mp["notice"][pre_pool], qlen_wo,
                                            k_pre)
        else:
            resume_raw = _kernel_on_preempt_slab(kernel, params, age_pre,
                                                 mp["notice"][pre_pool],
                                                 qlen_wo, layout, x)
        resume = pre_hit & resume_raw
        defect_pre = pre_hit & (~resume)
        price_p = eff_price[pre_pool]
    else:
        pre_slot = jnp.zeros((), jnp.int32)
        pre_hit = jnp.zeros((), jnp.bool_)
        age_pre = jnp.zeros((), jnp.float32)
        resume = jnp.zeros((), jnp.bool_)
        defect_pre = jnp.zeros((), jnp.bool_)
        price_p = jnp.zeros((), jnp.float32)

    if work is not None and preempt_on:
        # rollback: the resumed job restarts from its checkpoint and owes
        # the restart overhead before progress resumes.  In notice mode
        # the checkpoint saves current progress iff it fits the firing
        # pool's notice window — the PR-2 law, now priced in lost work.
        if work.ckpt == "notice":
            saved = resume & checkpoint_within_notice(
                wk["ckpt_time"], mp["notice"][pre_pool])
        else:
            saved = jnp.zeros((), jnp.bool_)
        prog_p = jnp.sum(jnp.where(iota == pre_slot, prog_new, 0.0))
        ckpt_p = jnp.sum(jnp.where(iota == pre_slot, ckpt_new, 0.0))
        ckpt_val = jnp.where(saved, jnp.maximum(ckpt_p, prog_p), ckpt_p)
        resume_vec = resume & (iota == pre_slot)
        prog_new = jnp.where(resume_vec, ckpt_val, prog_new)
        oh_new = jnp.where(resume_vec, wk["restart_overhead"], oh_new)
        ckpt_new = jnp.where(resume_vec, ckpt_val, ckpt_new)
        lost = jnp.where(resume, jnp.maximum(prog_p - ckpt_val, 0.0), 0.0)
        oh_inc = jnp.where(resume, wk["restart_overhead"], 0.0)
        ckpt_taken = ckpt_taken | (resume & saved)
    elif work is not None:
        lost = jnp.zeros((), jnp.float32)
        oh_inc = jnp.zeros((), jnp.float32)

    # ---- deadline: the minimal-budget job defects to on-demand ----
    defected = is_deadline
    age_defect = jnp.sum(jnp.where(iota == defect_slot, ages, 0.0))

    leave = complete_serve | defected | defect_pre
    leave_slot = jnp.where(served, serve_slot,
                           jnp.where(defected, defect_slot, pre_slot))

    join_mask = admit & (iota == join_slot)
    leave_mask = leave & (iota == leave_slot)
    resume_mask = resume & (iota == pre_slot)
    ages = jnp.where(join_mask | resume_mask, 0.0, ages)
    budgets = jnp.where(join_mask, budget,
                        jnp.where(resume_mask, INF, budgets))
    occ = (carry.occ | join_mask) & (~leave_mask)
    pool = jnp.where(join_mask, pool_choice, carry.pool)
    order = jnp.where(join_mask | resume_mask, carry.next_seq, carry.order)
    if work is not None:
        life_new = jnp.where(join_mask, 0.0, wk_c.life + dt)
        prog_new = jnp.where(join_mask, 0.0, prog_new)
        oh_new = jnp.where(join_mask, 0.0, oh_new)
        ckpt_new = jnp.where(join_mask, 0.0, ckpt_new)

    fire_s = is_spot & (iota_p == spot_pool)
    if layout is None:
        spot_draws = _sample_spot_clocks(market, k_spot, mp)
        job_draw = job.sample(k_job)
    else:
        spot_draws = _slab_spot_clocks(
            tuple(p.arrival for p in market.pools),
            layout.uniforms(x, layout.spot), mp["spot_scale"])
        job_draw = job.sample_u(layout.uniforms(x, layout.job))
    if ep is not None:
        # refresh draws live under the POST-event segment; boundary
        # crossings rescale the survived clocks exactly (memorylessness)
        seg_new = seg + is_boundary.astype(jnp.int32)
        inv_old = inv_avail(avail_row)
        inv_new = inv_avail(env_row(ep["avail"], seg_new))
        eff_hazard_new = mp["hazard"] * env_row(ep["hazard"], seg_new)
        spot_draws = spot_draws * inv_new
    else:
        eff_hazard_new = mp["hazard"]
    next_spot = jnp.where(fire_s, spot_draws, carry.next_spot - dt)
    if ep is not None:
        next_spot = jnp.where(is_boundary, next_spot * (inv_new / inv_old),
                              next_spot)
    if not preempt_on:
        next_preempt = carry.next_preempt
    elif layout is None:
        fire_p = is_pre & (iota_p == pre_pool)
        next_preempt = jnp.where(
            fire_p, sample_hazard_clocks(_market_tags(market), k_pre,
                                         eff_hazard_new),
            carry.next_preempt - dt)
        if ep is not None:
            next_preempt = jnp.where(
                is_boundary,
                next_preempt * clock_rescale(eff_hazard, eff_hazard_new),
                next_preempt)
    else:
        # scalar superposed clock: refresh Exp(Σ h_p) whenever ANY pool
        # fires (memorylessness makes the non-firing residuals fresh draws)
        next_preempt = jnp.where(
            is_pre, hazard_clock(eff_hazard_new,
                                 layout.uniforms(x, layout.preempt)[0]),
            carry.next_preempt - dt)
        if ep is not None:
            next_preempt = jnp.where(
                is_boundary,
                next_preempt * clock_rescale(jnp.sum(eff_hazard),
                                             jnp.sum(eff_hazard_new)),
                next_preempt)

    new_carry = MarketState(
        key=key,
        next_job=jnp.where(is_job, job_draw, carry.next_job - dt),
        next_spot=next_spot,
        next_preempt=next_preempt,
        ages=ages,
        budgets=budgets,
        occ=occ,
        pool=pool,
        order=order,
        next_seq=carry.next_seq + jnp.where(admit | resume, 1, 0),
        qlen=carry.qlen + jnp.where(admit, 1, 0) - jnp.where(leave, 1, 0),
    )
    completed = od_now | served | defected | defect_pre | resume
    new_stats = MarketWindowStats(
        jobs_arrived=stats.jobs_arrived + is_job.astype(jnp.int32),
        jobs_completed=stats.jobs_completed + completed.astype(jnp.int32),
        spot_served=stats.spot_served + served.astype(jnp.int32),
        ondemand=stats.ondemand
        + (od_now | defected | defect_pre).astype(jnp.int32),
        cost_sum=stats.cost_sum
        + jnp.where(served, price_s, 0.0)
        + jnp.where(od_now | defected | defect_pre, k_cost, 0.0)
        + jnp.where(pre_hit, price_p, 0.0),
        delay_sum=stats.delay_sum
        + jnp.where(served, wait_served, 0.0)
        + jnp.where(defected, age_defect, 0.0)
        + jnp.where(pre_hit, age_pre, 0.0),
        time_elapsed=stats.time_elapsed + dt,
        empty_time=stats.empty_time + jnp.where(carry.qlen == 0, dt, 0.0),
        spot_arrivals=stats.spot_arrivals + is_spot.astype(jnp.int32),
        spot_found_empty=stats.spot_found_empty
        + (is_spot & (~has_elig)).astype(jnp.int32),
        resumed=stats.resumed + resume.astype(jnp.int32),
        spot_cost=stats.spot_cost
        + jnp.where(served, price_s, 0.0)
        + jnp.where(pre_hit, price_p, 0.0),
        pool_served=stats.pool_served
        + (fire_s & served).astype(jnp.int32),
        pool_spot_arrivals=stats.pool_spot_arrivals
        + fire_s.astype(jnp.int32),
        pool_preempted=stats.pool_preempted
        + (pre_hit & (iota_p == pre_pool)).astype(jnp.int32),
    )
    if tel is not None:
        defect_pool = jnp.sum(jnp.where(iota == defect_slot, carry.pool, 0))
        loc = jnp.where(is_spot, spot_pool,
                        jnp.where(is_pre, pre_pool,
                                  jnp.where(is_deadline, defect_pool,
                                            pool_choice)))
        tstats = telemetry_update(
            tel, tstats, t=new_stats.time_elapsed, is_job=is_job,
            is_spot=is_spot, is_pre=is_pre, is_deadline=is_deadline,
            served=served, resume=resume, defected=defected, od_now=od_now,
            wait_sample=jnp.where(served, wait_served,
                                  jnp.where(defected, age_defect, age_pre)),
            wait_valid=served | defected | pre_hit,
            cost_inc=jnp.where(served, price_s, 0.0)
            + jnp.where(od_now | defected | defect_pre, k_cost, 0.0)
            + jnp.where(pre_hit, price_p, 0.0),
            cost_valid=served | od_now | defected | pre_hit,
            loc=loc, n_locs=n_pools, qlen=new_carry.qlen)
    out_stats = (new_stats, tstats) if tel is not None else new_stats
    out_carry = new_carry
    if ep is not None:
        estats = env_update(
            estats, is_boundary=is_boundary,
            kind_prev=env_row(ep["kind"], seg),
            kind_next=env_row(ep["kind"], seg_new), dt=dt, is_job=is_job,
            od_now=od_now, served=served, resumed=resume)
        new_env = EnvState(
            next_boundary=jnp.where(
                is_boundary,
                env_row(ep["t_end"], seg_new) - env_row(ep["t_end"], seg),
                env_c.next_boundary - dt),
            seg=seg_new)
        out_carry = (new_carry, new_env)
        out_stats = (out_stats, estats)
    if work is not None:
        life_def = jnp.sum(jnp.where(iota == defect_slot, wk_c.life + dt,
                                     0.0))
        rem_def = jnp.sum(jnp.where(iota == defect_slot, rem_tot, 0.0))
        life_pre = jnp.sum(jnp.where(iota == pre_slot, wk_c.life + dt, 0.0))
        rem_pre = jnp.sum(jnp.where(iota == pre_slot, rem_tot, 0.0))
        life_srv = jnp.sum(jnp.where(iota == serve_slot, wk_c.life + dt,
                                     0.0))
        od = wk["od_time"]
        # a job finishes at its last served unit or when it migrates to
        # on-demand; od finish time = life at migration + remaining work
        # × od_time (live migration — the preempted job's remaining work
        # is its PRE-rollback remainder, it does not re-lose progress by
        # leaving the spot market)
        miss = ((od_now & (wk["total_work"] * od > wk["deadline"]))
                | (defected & (life_def + rem_def * od > wk["deadline"]))
                | (defect_pre & (life_pre + rem_pre * od > wk["deadline"]))
                | (complete_serve & (life_srv > wk["deadline"])))
        panic = (defected & jnp.any((iota == defect_slot) & panic_armed)
                 if panic_armed is not None else jnp.zeros((), jnp.bool_))
        wstats = survival_update(
            wstats, admitted=is_job,
            finished=od_now | complete_serve | defected | defect_pre,
            missed=miss, checkpoint=ckpt_taken, panic=panic,
            work_done=done_inc, work_lost=lost,
            work_recomputed=lost + oh_inc, overhead_paid=oh_inc)
        return (out_carry, WorkState(prog=prog_new, oh=oh_new,
                                     ckpt=ckpt_new, life=life_new)), \
            (out_stats, wstats)
    return out_carry, out_stats


def _market_layout(job: ArrivalProcess, market: SpotMarket, kernel,
                   preempt_on: bool) -> SlabLayout:
    """Slab column map for the market loop: the spot span is the max
    ``u_dim`` across pools (all pools transform the same shared
    uniforms)."""
    return build_slab_layout(
        kernel, job_udim=process_udim(job),
        spot_udim=max(process_udim(p.arrival) for p in market.pools),
        n=market.n_pools, preempt_on=preempt_on, market=True)


def run_market_window(job: ArrivalProcess, market: SpotMarket, kernel,
                      rmax: int, preempt_on: bool, state: MarketState,
                      params, mp: dict, k_cost: jax.Array, n_events: int,
                      layout: SlabLayout | None = None,
                      tel: Telemetry | None = None, ep: dict | None = None,
                      work: WorkModel | None = None, wk: dict | None = None
                      ) -> tuple[MarketState, MarketWindowStats]:
    """Run ``n_events`` merged market events; one window of float32 sums."""
    step = functools.partial(_market_event, job, market, kernel, rmax,
                             preempt_on, layout, params=params, mp=mp,
                             k_cost=k_cost, tel=tel, ep=ep, work=work, wk=wk)
    zeros = _with_zeros(MarketWindowStats.zeros(market.n_pools), tel,
                        market.n_pools, env=ep is not None,
                        work=work is not None)
    if layout is None:
        return _scan_window(step, zeros, state, n_events)
    return _scan_window_slab(lambda c, s, x: step(c, s, x=x), zeros, state,
                             n_events, layout.n_cols,
                             paired=(ep is not None) or (work is not None))


def run_market_chunked(job: ArrivalProcess, market: SpotMarket, kernel,
                       rmax: int, preempt_on: bool, state: MarketState,
                       params, mp: dict, k_cost: jax.Array, n_events: int,
                       chunk_events: int, layout: SlabLayout | None = None,
                       tel: Telemetry | None = None, ep: dict | None = None,
                       work: WorkModel | None = None, wk: dict | None = None
                       ) -> tuple[MarketState, MarketWindowStats]:
    step = functools.partial(_market_event, job, market, kernel, rmax,
                             preempt_on, layout, params=params, mp=mp,
                             k_cost=k_cost, tel=tel, ep=ep, work=work, wk=wk)
    zeros = _with_zeros(MarketWindowStats.zeros(market.n_pools), tel,
                        market.n_pools, env=ep is not None,
                        work=work is not None)
    rebase = _rebase_for(ep, work)
    if layout is None:
        return _scan_chunked(step, zeros, state, n_events, chunk_events,
                             rebase=rebase)
    return _scan_chunked_slab(lambda c, s, x: step(c, s, x=x), zeros, state,
                              n_events, chunk_events, layout.n_cols,
                              paired=(ep is not None) or (work is not None),
                              rebase=rebase)


@functools.partial(
    jax.jit,
    static_argnames=("job", "market", "kernel", "rmax", "preempt_on",
                     "n_events", "chunk_events", "burn_in", "rng", "tel",
                     "work"),
)
def _run_market_sim_jit(job, market, kernel, rmax, preempt_on, n_events,
                        chunk_events, burn_in, rng, params, mp, k_cost, key,
                        tel=None, ep=None, work=None, wk=None):
    layout = (_market_layout(job, market, kernel, preempt_on)
              if rng == "slab" else None)
    state = init_market_state(key, job, market, rmax, mp, preempt_on,
                              scalar_preempt=layout is not None, ep=ep)
    if ep is not None:
        state = (state, init_env_state(ep))
    if work is not None:
        state = (state, init_work_state(rmax))
    if burn_in:
        state, _ = run_market_window(job, market, kernel, rmax, preempt_on,
                                     state, params, mp, k_cost, burn_in,
                                     layout=layout, tel=tel, ep=ep,
                                     work=work, wk=wk)
        state = _rebase_for(ep, work)(state)
    return run_market_chunked(job, market, kernel, rmax, preempt_on, state,
                              params, mp, k_cost, n_events, chunk_events,
                              layout=layout, tel=tel, ep=ep, work=work,
                              wk=wk)


@functools.partial(
    jax.jit,
    static_argnames=("job", "market", "kernel", "rmax", "preempt_on",
                     "n_events", "chunk_events", "burn_in", "rng", "tel",
                     "work"),
)
def _run_market_sweep_jit(job, market, kernel, rmax, preempt_on, n_events,
                          chunk_events, burn_in, rng, params, mp, k_cost,
                          keys, tel=None, ep=None, work=None, wk=None):
    """(grid × pools-config × seeds) fleet as one nested-vmap XLA program
    (broadcast ``in_axes``; see :func:`_flat_lane_args`)."""
    layout = (_market_layout(job, market, kernel, preempt_on)
              if rng == "slab" else None)

    def one(p, m, kc, key):
        state = init_market_state(key, job, market, rmax, m, preempt_on,
                                  scalar_preempt=layout is not None, ep=ep)
        if ep is not None:
            state = (state, init_env_state(ep))
        if work is not None:
            state = (state, init_work_state(rmax))
        if burn_in:
            state, _ = run_market_window(job, market, kernel, rmax,
                                         preempt_on, state, p, m, kc,
                                         burn_in, layout=layout, tel=tel,
                                         ep=ep, work=work, wk=wk)
            state = _rebase_for(ep, work)(state)
        _, stats = run_market_chunked(job, market, kernel, rmax, preempt_on,
                                      state, p, m, kc, n_events,
                                      chunk_events, layout=layout, tel=tel,
                                      ep=ep, work=work, wk=wk)
        return stats

    per_seeds = jax.vmap(one, in_axes=(None, None, None, 0))
    return jax.vmap(per_seeds, in_axes=(0, 0, 0, None))(params, mp, k_cost,
                                                        keys)


@functools.partial(
    jax.jit,
    static_argnames=("job", "market", "kernel", "rmax", "preempt_on",
                     "n_events", "chunk_events", "burn_in", "tile",
                     "interpret", "executor", "rng", "tel", "work"),
)
def _run_market_sweep_pallas_jit(job, market, kernel, rmax, preempt_on,
                                 n_events, chunk_events, burn_in, tile,
                                 interpret, params, mp, k_cost, keys,
                                 executor="pallas", rng="split", tel=None,
                                 ep=None, work=None, wk=None):
    """The market fleet through the same batched-event kernel family: the
    per-pool ``next_spot``/``next_preempt`` clock vectors become
    (tile, n_pools) VMEM blocks and :func:`_market_event` is the vmap-ed
    kernel body — bit-for-bit the ``executor="ref"`` scan oracle; integer
    stats bitwise / float sums to ~ulp vs :func:`_run_market_sweep_jit`
    (see the module docstring).  Under ``rng="slab"`` the slab arrives as
    a (tile, 1, window_events, n_cols) input block per window and the
    kernel performs no RNG at all."""
    g, s = k_cost.shape[0], keys.shape[0]
    (params_f, mp_f), k_f, keys_f = _flat_lane_args((params, mp), k_cost,
                                                    keys)
    params_b = {"params": params_f, "mp": mp_f, "k": k_f}
    layout = (_market_layout(job, market, kernel, preempt_on)
              if rng == "slab" else None)
    state0 = jax.vmap(
        lambda key, m: init_market_state(
            key, job, market, rmax, m, preempt_on,
            scalar_preempt=layout is not None,
            ep=ep))(keys_f, mp_f)
    plan = _window_plan(n_events, chunk_events, burn_in)

    if layout is not None:
        slab = _lane_slabs(state0, plan, layout,
                           compiled=executor == "pallas" and not interpret)
    else:
        slab = None
    if ep is not None:
        params_b["ep"], es0 = _env_lane_blocks(ep, keys_f.shape[0])
        state0 = (state0, es0)
    if work is not None:
        params_b["wk"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (keys_f.shape[0],)), wk)
        state0 = (state0, init_work_state(rmax, keys_f.shape[0]))

    if layout is not None:
        def step(carry, stats, p, x):
            return _market_event(job, market, kernel, rmax, preempt_on,
                                 layout, carry, stats, p["params"], p["mp"],
                                 p["k"], x=x, tel=tel, ep=p.get("ep"),
                                 work=work, wk=p.get("wk"))
    else:
        def step(carry, stats, p):
            return _market_event(job, market, kernel, rmax, preempt_on,
                                 None, carry, stats, p["params"], p["mp"],
                                 p["k"], tel=tel, ep=p.get("ep"),
                                 work=work, wk=p.get("wk"))

    zeros = _with_zeros(MarketWindowStats.zeros(market.n_pools), tel,
                        market.n_pools, env=ep is not None,
                        work=work is not None)
    epilogue = _rebase_for(ep, work)
    if executor == "ref":
        _, stats = batched_event_windows_ref(
            step, state0, params_b, zeros, plan, slab=slab,
            epilogue=epilogue)
    else:
        _, stats = batched_events(
            step, state0, params_b, zeros, plan, slab=slab, tile=tile,
            interpret=interpret, epilogue=epilogue)
    if burn_in:
        stats = jax.tree.map(lambda x: x[:, 1:], stats)
    return _unflatten_lanes(stats, g, s)


def _market_sweep_lanes(job, market, kernel, rmax, preempt_on, n_events,
                        chunk_events, burn_in, tile, interpret, params_f,
                        mp_f, k_f, keys_f, *, executor, rng, tel=None,
                        ep=None, work=None, wk=None):
    """One shard of flat market lanes through any executor (cf.
    :func:`_sweep_lanes`; the pools-config tree ``mp_f`` is a per-lane
    grid axis exactly as in :func:`_run_market_sweep_pallas_jit`)."""
    layout = (_market_layout(job, market, kernel, preempt_on)
              if rng == "slab" else None)
    if executor == "xla":
        def one(p, m, kc, key):
            state = init_market_state(key, job, market, rmax, m, preempt_on,
                                      scalar_preempt=layout is not None,
                                      ep=ep)
            if ep is not None:
                state = (state, init_env_state(ep))
            if work is not None:
                state = (state, init_work_state(rmax))
            if burn_in:
                state, _ = run_market_window(job, market, kernel, rmax,
                                             preempt_on, state, p, m, kc,
                                             burn_in, layout=layout, tel=tel,
                                             ep=ep, work=work, wk=wk)
                state = _rebase_for(ep, work)(state)
            _, stats = run_market_chunked(job, market, kernel, rmax,
                                          preempt_on, state, p, m, kc,
                                          n_events, chunk_events,
                                          layout=layout, tel=tel, ep=ep,
                                          work=work, wk=wk)
            return stats

        return jax.vmap(one)(params_f, mp_f, k_f, keys_f)

    params_b = {"params": params_f, "mp": mp_f, "k": k_f}
    state0 = jax.vmap(
        lambda key, m: init_market_state(
            key, job, market, rmax, m, preempt_on,
            scalar_preempt=layout is not None, ep=ep))(keys_f, mp_f)
    plan = _window_plan(n_events, chunk_events, burn_in)
    slab = None if layout is None else _lane_slabs(
        state0, plan, layout, compiled=executor == "pallas" and not interpret)
    if ep is not None:
        params_b["ep"], es0 = _env_lane_blocks(ep, keys_f.shape[0])
        state0 = (state0, es0)
    if work is not None:
        params_b["wk"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (keys_f.shape[0],)), wk)
        state0 = (state0, init_work_state(rmax, keys_f.shape[0]))

    if layout is not None:
        def step(carry, stats, p, x):
            return _market_event(job, market, kernel, rmax, preempt_on,
                                 layout, carry, stats, p["params"], p["mp"],
                                 p["k"], x=x, tel=tel, ep=p.get("ep"),
                                 work=work, wk=p.get("wk"))
    else:
        def step(carry, stats, p):
            return _market_event(job, market, kernel, rmax, preempt_on,
                                 None, carry, stats, p["params"], p["mp"],
                                 p["k"], tel=tel, ep=p.get("ep"),
                                 work=work, wk=p.get("wk"))

    zeros = _with_zeros(MarketWindowStats.zeros(market.n_pools), tel,
                        market.n_pools, env=ep is not None,
                        work=work is not None)
    epilogue = _rebase_for(ep, work)
    if executor == "ref":
        _, stats = batched_event_windows_ref(
            step, state0, params_b, zeros, plan, slab=slab, epilogue=epilogue)
    else:
        _, stats = batched_events(
            step, state0, params_b, zeros, plan, slab=slab, tile=tile,
            interpret=interpret, epilogue=epilogue)
    if burn_in:
        stats = jax.tree.map(lambda x: x[:, 1:], stats)
    return stats


@functools.partial(
    jax.jit,
    static_argnames=("job", "market", "kernel", "rmax", "preempt_on",
                     "n_events", "chunk_events", "burn_in", "tile",
                     "interpret", "mesh", "executor", "rng", "tel", "work"),
)
def _run_market_sweep_sharded_jit(job, market, kernel, rmax, preempt_on,
                                  n_events, chunk_events, burn_in, tile,
                                  interpret, mesh, params, mp, k_cost, keys,
                                  executor="xla", rng="split", tel=None,
                                  ep=None, work=None, wk=None):
    """The market fleet lane-partitioned across a 1-D device mesh (cf.
    :func:`_run_sweep_sharded_jit`)."""
    g, s = k_cost.shape[0], keys.shape[0]
    (params_f, mp_f), k_f, keys_f = _flat_lane_args((params, mp), k_cost,
                                                    keys)
    lanes = g * s
    params_f, mp_f, k_f, keys_f = pad_lanes((params_f, mp_f, k_f, keys_f),
                                            _pad_count(lanes, mesh))
    spec, rspec = lane_spec(mesh), jax.sharding.PartitionSpec()

    def local(pf, mf, kf, keysf, ep_, wk_):
        return _market_sweep_lanes(job, market, kernel, rmax, preempt_on,
                                   n_events, chunk_events, burn_in, tile,
                                   interpret, pf, mf, kf, keysf,
                                   executor=executor, rng=rng, tel=tel,
                                   ep=ep_, work=work, wk=wk_)

    stats = shard_map_1d(local, mesh=mesh,
                         in_specs=(spec, spec, spec, spec, rspec, rspec),
                         out_specs=spec)(params_f, mp_f, k_f, keys_f, ep, wk)
    if lanes != keys_f.shape[0]:
        stats = jax.tree.map(lambda x: x[:lanes], stats)
    return _unflatten_lanes(stats, g, s)


def summarize_market(stats: MarketWindowStats,
                     telemetry: Telemetry | None = None,
                     env: EnvTimeline | None = None, work=None) -> dict:
    """Float64 chunk reduction + market-specific derived statistics.

    Extends :func:`summarize`'s dict with preemption counters, spot spend,
    and per-pool served/arrival/utilization arrays (trailing pool axis).
    The chunk axis is the last axis for scalar accumulators and the
    second-to-last for per-pool vectors.  With ``telemetry``, ``stats`` is
    the ``(base, telemetry)`` pair and the telemetry fields are appended
    (base keys unchanged; see :func:`summarize`).  With ``env``, the env
    block rides outermost and the shock counters are appended.  With
    ``work``, the survival ledger rides outermost of all and its job-level
    fields are appended.
    """
    wstats = None
    if work is not None:
        stats, wstats = stats
    estats = None
    if env is not None:
        stats, estats = stats
    tstats = None
    if telemetry is not None:
        stats, tstats = stats
    n_common = len(WindowStats._fields)
    out = summarize(WindowStats(*stats[:n_common]))

    def _red(name):
        x = getattr(stats, name)
        axis = -2 if name in _POOL_FIELDS else -1
        return np.asarray(x, np.float64).sum(axis=axis)

    resumed = _red("resumed")
    spot_cost = _red("spot_cost")
    pool_served = _red("pool_served")
    pool_arrivals = _red("pool_spot_arrivals")
    pool_preempted = _red("pool_preempted")
    # per-JOB statistics: jobs_completed counts *legs* under preemption (a
    # checkpointed revocation closes one leg; the retry completes later),
    # which is the right window statistic for Algorithm 1 but not the
    # paper's E[C].  Jobs leave the system only via spot service or
    # on-demand, so dividing the same cost/delay totals by final
    # completions gives true per-job averages (identical when resumed = 0).
    cost_sum = _red("cost_sum")
    delay_sum = _red("delay_sum")
    final = np.maximum(_red("spot_served") + _red("ondemand"), 1.0)
    out.update({
        "preemptions": pool_preempted.sum(axis=-1),
        "resumed": resumed,
        "spot_cost": spot_cost,
        "avg_cost_job": cost_sum / final,
        "avg_delay_job": delay_sum / final,
        "pool_served": pool_served,
        "pool_spot_arrivals": pool_arrivals,
        "pool_preempted": pool_preempted,
        "pool_utilization": pool_served / np.maximum(pool_arrivals, 1.0),
    })
    if telemetry is not None:
        out = _merge_telemetry(out, telemetry, tstats, stats.time_elapsed)
    if estats is not None:
        out.update(summarize_env(estats))
    if wstats is not None:
        out.update(summarize_survival(wstats))
    return out


def _broadcast_config_params(n: int, cfg: dict, overrides: dict,
                             grid_shape: tuple) -> dict:
    """Merge config overrides into a traced per-pool/per-region params dict.

    Each override broadcasts to ``grid_shape + (n,)``: scalars fill every
    entry, ``(n,)`` vectors fix a config, ``grid_shape + (n,)`` arrays sweep
    the configuration itself.  Shared by the market (pools axis) and region
    (regions axis) sweep entry points; non-overridden keys keep their dtype
    (the region config carries an int32 ``rmax`` vector).
    """
    for name, val in overrides.items():
        if val is None:
            continue
        v = jnp.asarray(val, jnp.float32)
        if v.ndim == 0:
            v = jnp.broadcast_to(v, (n,))
        cfg[name] = v
    return {name: jnp.broadcast_to(v, grid_shape + (n,))
            .reshape((-1, n)) for name, v in cfg.items()}


@functools.partial(
    jax.jit,
    static_argnames=("job", "market", "kernel", "rmax", "preempt_on",
                     "n_events", "chunk_events", "burn_in", "tile",
                     "interpret", "mesh", "impl", "rng", "tel", "work",
                     "grid_shape", "n_seeds"),
)
def _market_sweep_call(job, market, kernel, rmax, preempt_on, n_events,
                       chunk_events, burn_in, tile, interpret, mesh, impl,
                       rng, tel, work, grid_shape, n_seeds, params, mp, k,
                       overrides, key, ep=None, wk=None):
    """:func:`run_market_sweep`'s argument prep and executor as ONE
    program, so an answer is one dispatch: the float32 casts, the grid
    broadcast and flattening, the pools-config overrides and the seed-key
    split run under the trace, then the executor that ``impl`` and
    ``mesh`` select (jitted itself, so inlined).

    ``mp`` (:meth:`SpotMarket.host_params`) enters as an operand, never
    as a constant: XLA rewrites a division by a constant into a
    multiplication by its reciprocal, which would move the clocks
    ``init_market_state`` draws by an ulp."""
    flat = lambda x: jnp.broadcast_to(jnp.asarray(x, jnp.float32),
                                      grid_shape).reshape(-1)
    params_flat = jax.tree.map(flat, params)
    k_flat = flat(k)
    mp_flat = _broadcast_config_params(market.n_pools, mp, overrides,
                                       grid_shape)
    keys = jax.random.split(key, n_seeds)
    if mesh is not None:
        return _run_market_sweep_sharded_jit(
            job, market, kernel, rmax, preempt_on, n_events, chunk_events,
            burn_in, tile, interpret, mesh, params_flat, mp_flat, k_flat,
            _raw_keys(keys), executor=impl, rng=rng, tel=tel, ep=ep,
            work=work, wk=wk)
    if impl == "xla":
        return _run_market_sweep_jit(
            job, market, kernel, rmax, preempt_on, n_events, chunk_events,
            burn_in, rng, params_flat, mp_flat, k_flat, keys, tel=tel, ep=ep,
            work=work, wk=wk)
    return _run_market_sweep_pallas_jit(
        job, market, kernel, rmax, preempt_on, n_events, chunk_events,
        burn_in, tile, interpret, params_flat, mp_flat, k_flat,
        _raw_keys(keys), executor=impl, rng=rng, tel=tel, ep=ep, work=work,
        wk=wk)


def run_market_sim(
    job: ArrivalProcess,
    market: SpotMarket,
    kernel,
    params=None,
    *,
    k: float = 10.0,
    n_events: int,
    key: jax.Array,
    rmax: int = 64,
    burn_in: int = 0,
    chunk_events: int | None = DEFAULT_CHUNK_EVENTS,
    impl: str = "xla",
    rng: str = "split",
    tile: int = 256,
    interpret: bool | None = None,
    telemetry: Telemetry | None = None,
    env: EnvTimeline | None = None,
    work: WorkModel | None = None,
) -> dict:
    """Run one market policy at one parameter point; scalar long-run stats.

    A degenerate market (:meth:`SpotMarket.is_degenerate`) with a legacy
    kernel reproduces :func:`run_sim` bit-for-bit per seed.  ``chunk_events``
    / ``impl`` / ``rng`` behave exactly as in :func:`run_sim`; ``env``
    attaches an :class:`~repro.core.env.EnvTimeline` (per-pool price /
    hazard / availability segments) exactly as in :func:`run_sim`;
    ``work`` (a :class:`repro.core.work.WorkModel`) attaches the work
    structure — checkpoint-priced recovery, restart overhead, deadlines —
    and the survival ledger (module docstring of :mod:`repro.core.work`).
    """
    with EntrySpan(f"repro.run_market_sim[{impl}]") as call:
        market = as_market(market)
        params = {} if params is None else params
        _check_rng(rng)
        _check_telemetry(telemetry)
        _check_env(env)
        _check_work(work, kernel)
        _check_run_shape("run_market_sim", n_events, burn_in)
        interp = _resolve_interpret("run_market_sim", impl, rng, interpret)
        mp = market.params()
        ep = _env_params(env, market.n_pools)
        wk = None if work is None else work.params()
        chunk = (n_events if chunk_events is None
                 else min(chunk_events, n_events))
        call.phase("dispatch")
        if impl in ("pallas", "ref"):
            stats = _run_market_sweep_pallas_jit(
                job, market, kernel, rmax, market.preemptible, n_events,
                chunk, burn_in, tile,
                interp,
                jax.tree.map(lambda x: jnp.asarray(x)[None], params),
                jax.tree.map(lambda x: jnp.asarray(x)[None], mp),
                jnp.float32(k)[None], _raw_keys(key)[None], executor=impl,
                rng=rng, tel=telemetry, ep=ep, work=work, wk=wk)
            stats = jax.tree.map(lambda x: x[0, 0], stats)
        elif impl == "xla":
            _, stats = _run_market_sim_jit(job, market, kernel, rmax,
                                           market.preemptible, n_events,
                                           chunk, burn_in, rng, params, mp,
                                           jnp.float32(k), key,
                                           tel=telemetry, ep=ep, work=work,
                                           wk=wk)
        else:
            raise ValueError(
                f"unknown impl {impl!r} (expected 'xla'|'pallas'|'ref')")
        stats = call.to_host(stats)
        return {name: _scalar_or_array(v)
                for name, v in summarize_market(stats, telemetry, env=env,
                                                work=work).items()}


def run_market_sweep(
    job: ArrivalProcess,
    market: SpotMarket,
    kernel,
    params=None,
    *,
    k: float | np.ndarray | jax.Array = 10.0,
    prices=None,
    hazards=None,
    notices=None,
    spot_scales=None,
    n_events: int,
    key: jax.Array,
    n_seeds: int = 1,
    rmax: int = 64,
    burn_in: int = 0,
    chunk_events: int | None = DEFAULT_CHUNK_EVENTS,
    impl: str = "xla",
    rng: str = "split",
    tile: int = 256,
    interpret: bool | None = None,
    telemetry: Telemetry | None = None,
    env: EnvTimeline | None = None,
    work: WorkModel | None = None,
    shard: str = "none",
    mesh=None,
) -> dict:
    """Run a (params × k × pools-config × seeds) grid as ONE jitted call.

    ``params`` leaves and ``k`` broadcast to a common grid shape exactly as
    in :func:`run_sweep`.  ``prices``/``hazards``/``notices``/``spot_scales``
    optionally override the market's static pool configuration per grid
    point: a scalar applies to every pool, a ``(P,)`` vector fixes one
    config, and a ``grid_shape + (P,)`` array sweeps the pool configuration
    inside the same compiled program (the pools-config axis of the grid).

    ``impl``/``tile``/``interpret`` select the executor exactly as in
    :func:`run_sweep`; the Pallas path widens the VMEM-resident state tile
    with the (tile, n_pools) clock vectors — bit-for-bit the ``"ref"``
    oracle, integer stats bitwise / float sums to ~ulp vs ``"xla"`` (see
    the module docstring's executor contract).  ``shard="lanes"``
    partitions the flattened lane axis across a 1-D device mesh exactly
    as in :func:`run_sweep` (pools-config lanes ride along).

    Returns :func:`summarize_market`'s dict; scalar statistics are shaped
    ``grid_shape + (n_seeds,)`` and per-pool statistics
    ``grid_shape + (n_seeds, P)``.
    """
    with EntrySpan(f"repro.run_market_sweep[{impl}]") as call:
        market = as_market(market)
        n = market.n_pools
        params = {} if params is None else params
        _check_rng(rng)
        _check_telemetry(telemetry)
        _check_env(env)
        _check_work(work, kernel)
        _check_shard("run_market_sweep", shard, mesh)
        _check_run_shape("run_market_sweep", n_events, burn_in)
        interp = _resolve_interpret("run_market_sweep", impl, rng, interpret)
        _check_loc_overrides("run_market_sweep", n, "pool", prices=prices,
                             hazards=hazards, notices=notices,
                             spot_scales=spot_scales)
        if impl not in ("xla", "pallas", "ref"):
            raise ValueError(
                f"unknown impl {impl!r} (expected 'xla'|'pallas'|'ref')")
        ep = _env_params(env, n)
        wk = None if work is None else work.params()
        overrides = {"price": prices, "hazard": hazards, "notice": notices,
                     "spot_scale": spot_scales}
        # shapes only: np.shape builds no device array
        override_shapes = [np.shape(v)[:-1] for v in overrides.values()
                           if v is not None and np.ndim(v) > 1]
        grid_shape = np.broadcast_shapes(
            np.shape(k), *(np.shape(x) for x in jax.tree.leaves(params)),
            *override_shapes)
        preempt_on = market.preemptible or hazards is not None
        chunk = (n_events if chunk_events is None
                 else min(chunk_events, n_events))
        if shard == "lanes" and mesh is None:
            mesh = lane_mesh()
        call.phase("dispatch")
        stats = _market_sweep_call(
            job, market, kernel, rmax, preempt_on, n_events, chunk, burn_in,
            tile, interp, mesh, impl, rng, telemetry, work, grid_shape,
            n_seeds, params, market.host_params(), k, overrides, key, ep=ep,
            wk=wk)
        stats = call.to_host(stats)
        out = summarize_market(stats, telemetry, env=env, work=work)
        return _reshape_sweep(out, grid_shape, n_seeds)


# ===========================================================================
# Multi-region routing: N queues, per-region clocks, routing at admission
# ===========================================================================
#
# Third traversal of the event-loop architecture (PR 4).  The region loop
# widens the market loop one more level: the scalar job clock becomes a
# per-region ``next_job`` vector, the pool clock vectors become per-region
# supply clocks (one pool per region — exactly the PR-2 market clocks,
# re-indexed), and the single ``(rmax,)`` queue becomes N per-region
# ``(rmax_r,)`` partitions packed as one ``(sum rmax_r,)`` slot array with a
# *static* slot→region map.  The kernel protocol gains a routing hook
# (``route(params, qlens, region_state, key) -> region``, see
# repro.core.regions); the admission law then runs against the TARGET
# region's queue length, so each region runs a per-region instance of the
# paper's policy.
#
# Event-time ties resolve spot > preempt > deadline > job (the PR-2 order);
# ties between regions resolve by position (argmin), measure-zero for
# continuous samplers.
#
# With a degenerate topology (1 region, zero hazard, unit price) and a
# kernel without a ``route`` hook, every expression below reduces bitwise
# to the market loop's (and hence, by the PR-2 ledger, to the PR-1 engine):
# the routing machinery is statically removed (no extra key split, target =
# home = 0), the per-region min/argmin over length-1 vectors are exact
# identities, the static all-zero slot→region map makes every eligibility
# mask equal the occupancy mask, and the extra stat terms accumulate into
# separate fields.  tests/test_core_regions.py freezes that contract
# against run_sim/run_sweep AND run_market_sim/run_market_sweep under all
# three executors.


class RegionWindowStats(NamedTuple):
    """Per-window accumulators for the region loop.

    The first ten fields mirror :class:`WindowStats` exactly (same order,
    same accumulation semantics); ``resumed``/``spot_cost`` mirror the
    market tail; the per-region counters close the set.  ``region_jobs``
    counts arrivals by HOME region; ``region_routed`` counts admissions by
    TARGET region — their difference is the cross-region flow the routing
    hook created (``routed_home`` tracks the non-crossing admissions).
    """

    jobs_arrived: jax.Array
    jobs_completed: jax.Array
    spot_served: jax.Array
    ondemand: jax.Array
    cost_sum: jax.Array
    delay_sum: jax.Array
    time_elapsed: jax.Array
    empty_time: jax.Array
    spot_arrivals: jax.Array
    spot_found_empty: jax.Array
    resumed: jax.Array  # i32: preempted legs that checkpointed + re-queued
    spot_cost: jax.Array  # f32: spend on region spot (incl. partial legs)
    routed_home: jax.Array  # i32: admissions whose target == home region
    region_served: jax.Array  # (R,) i32 completions per region
    region_spot_arrivals: jax.Array  # (R,) i32 slot arrivals per region
    region_preempted: jax.Array  # (R,) i32 preemption hits per region
    region_jobs: jax.Array  # (R,) i32 job arrivals per HOME region
    region_routed: jax.Array  # (R,) i32 admissions per TARGET region

    @staticmethod
    def zeros(n_regions: int) -> "RegionWindowStats":
        z = jnp.zeros((), jnp.float32)
        zi = jnp.zeros((), jnp.int32)
        zr = jnp.zeros((n_regions,), jnp.int32)
        return RegionWindowStats(zi, zi, zi, zi, z, z, z, z, zi, zi,
                                 zi, z, zi, zr, zr, zr, zr, zr)


_REGION_FIELDS = frozenset({"region_served", "region_spot_arrivals",
                            "region_preempted", "region_jobs",
                            "region_routed"})


class RegionState(NamedTuple):
    key: jax.Array
    next_job: jax.Array  # (R,) per-region job-arrival clocks
    next_spot: jax.Array  # (R,) per-region spot-slot clocks
    next_preempt: jax.Array  # (R,) per-region preemption clocks (INF = never)
    ages: jax.Array  # (S,) packed slots, S = sum rmax_r
    budgets: jax.Array  # (S,)
    occ: jax.Array  # (S,) bool
    order: jax.Array  # (S,) int32 join sequence number
    next_seq: jax.Array
    qlen: jax.Array  # (R,) int32 queued jobs per region


def _slot_region_iota(topo: RegionTopology, iota_s: jax.Array) -> jax.Array:
    """The static slot→region map as ops on an iota (no array constants:
    inline jnp constants would be hoisted as consts, which pallas_call
    rejects — same rule as the module-level np scalars)."""
    reg = jnp.zeros_like(iota_s)
    for off in topo.slot_offsets()[1:]:
        reg = reg + (iota_s >= np.int32(off)).astype(jnp.int32)
    return reg


def _region_tags(topo: RegionTopology) -> tuple:
    return tuple(r.tag for r in topo.regions)


def _sample_job_clocks(topo: RegionTopology, k_job: jax.Array,
                       rp: dict) -> jax.Array:
    """Per-region job clock refresh via the shared tag-folded plumbing
    (:func:`repro.core.clocks.sample_clock_vector`): the 1-region topology
    uses ``k_job`` directly, the PR-1/PR-2 key layout, so the degenerate
    engine is bit-for-bit the PR-3 engine."""
    return sample_clock_vector(tuple(r.job for r in topo.regions),
                               _region_tags(topo), k_job, rp["job_scale"])


def _sample_region_spot_clocks(topo: RegionTopology, k_spot: jax.Array,
                               rp: dict) -> jax.Array:
    return sample_clock_vector(tuple(r.spot for r in topo.regions),
                               _region_tags(topo), k_spot, rp["spot_scale"])


def init_region_state(key: jax.Array, topo: RegionTopology, rp: dict,
                      preempt_on: bool,
                      scalar_preempt: bool = False,
                      ep: dict | None = None) -> RegionState:
    """``scalar_preempt`` (the ``rng="slab"`` representation) carries ONE
    superposed preemption clock — min of the per-region init draws, exactly
    ``Exp(Σ h_r)``; see :func:`init_market_state`.  ``ep`` places the
    initial supply clocks under segment 0 (exact no-op on a constant
    timeline); job clocks are never modulated."""
    kj, ks, kc = jax.random.split(key, 3)
    n, s = topo.n_regions, topo.total_slots
    hazard0 = (rp["hazard"] if ep is None
               else rp["hazard"] * ep["hazard"][0])
    if preempt_on:
        next_preempt = sample_hazard_clocks(
            _region_tags(topo), jax.random.fold_in(ks, 2**31 - 1),
            hazard0)
        if scalar_preempt:
            next_preempt = jnp.min(next_preempt, keepdims=True)
    else:
        next_preempt = jnp.full((1 if scalar_preempt else n,), INF,
                                jnp.float32)
    next_job = _sample_job_clocks(topo, kj, rp)
    next_spot = _sample_region_spot_clocks(topo, ks, rp)
    if ep is not None:
        next_spot = next_spot * inv_avail(ep["avail"][0])
    return RegionState(
        key=kc,
        next_job=next_job,
        next_spot=next_spot,
        next_preempt=next_preempt,
        ages=jnp.zeros((s,), jnp.float32),
        budgets=jnp.full((s,), INF, jnp.float32),
        occ=jnp.zeros((s,), jnp.bool_),
        order=jnp.zeros((s,), jnp.int32),
        next_seq=jnp.zeros((), jnp.int32),
        qlen=jnp.zeros((n,), jnp.int32),
    )


def _kernel_region_admit(kernel, params, qlen_t, view: RegionView, key):
    """Run the admission law against the target region's queue length.

    Market-aware kernels (``admit_market``) see the regions as their pools
    (one supply pool per region — the :class:`PoolState` vectors ARE the
    region vectors); their pool choice is ignored in favour of the routing
    decision.  Legacy kernels call ``admit`` with the PR-1 key layout.
    """
    if hasattr(kernel, "admit_market"):
        ps = PoolState(price=view.price, hazard=view.hazard,
                       notice=view.notice, rate=view.rate,
                       qlen_pool=view.qlen_region)
        admit, budget, _pool = kernel.admit_market(params, qlen_t, ps, key)
        return admit, budget
    return kernel.admit(params, qlen_t, key)


def _kernel_region_admit_slab(kernel, params, qlen_t, view: RegionView,
                              layout: SlabLayout, x):
    """Slab-stream twin of :func:`_kernel_region_admit`."""
    if layout.market_admit:
        ps = PoolState(price=view.price, hazard=view.hazard,
                       notice=view.notice, rate=view.rate,
                       qlen_pool=view.qlen_region)
        if layout.admit_mode == "u":
            admit, budget, _pool = kernel.admit_market_u(
                params, qlen_t, ps, layout.uniforms(x, layout.admit))
        else:
            admit, budget, _pool = kernel.admit_market(
                params, qlen_t, ps, synth_key(layout.bits(x, layout.admit)))
        return admit, budget
    return _admit_slab(kernel, params, qlen_t, layout, x)


def _kernel_route_slab(kernel, params, qlens, view: RegionView,
                       layout: SlabLayout, x):
    if layout.route_mode == "u":
        return kernel.route_u(params, qlens, view,
                              layout.uniforms(x, layout.route))
    return kernel.route(params, qlens, view,
                        synth_key(layout.bits(x, layout.route)))


def _region_event(topo: RegionTopology, kernel, preempt_on: bool,
                  layout: SlabLayout | None, carry: RegionState,
                  stats: RegionWindowStats, params, rp: dict,
                  k_cost: jax.Array, x: jax.Array | None = None,
                  tel: Telemetry | None = None, ep: dict | None = None,
                  work: WorkModel | None = None, wk: dict | None = None
                  ) -> tuple[RegionState, RegionWindowStats]:
    """One merged event: job arrival (in some region) / region spot slot /
    region preemption / wait deadline.  Same dense one-hot-select style as
    :func:`_engine_event` (see the note there on scatter vs select under
    vmap); expression structure deliberately mirrors :func:`_market_event`
    so the degenerate reduction is auditable term by term — including the
    slab stream's superposed scalar preemption clock (``layout`` not None).
    ``tel`` appends the telemetry fold exactly as in :func:`_engine_event`
    (base expressions untouched); the event locus is the firing region.
    ``ep`` threads the environment timeline exactly as in
    :func:`_market_event` (regions are the locations; the demand-side
    ``next_job`` clocks are deliberately NOT modulated — supply shocks
    perturb the market, not the workload).
    ``work``/``wk`` thread the work axis exactly as in
    :func:`_market_event` (the packed slot array carries the work
    structure; rollbacks price the region's notice window).
    """
    n_regions, n_slots = topo.n_regions, topo.total_slots
    has_route = hasattr(kernel, "route")
    if work is not None:
        carry, wk_c = carry
        stats, wstats = stats
    if ep is not None:
        carry, env_c = carry
        stats, estats = stats
        seg = env_c.seg
        avail_row = env_row(ep["avail"], seg)
        eff_hazard = rp["hazard"] * env_row(ep["hazard"], seg)
        eff_price = rp["price"] * env_row(ep["price"], seg)
    else:
        eff_hazard = rp["hazard"]
        eff_price = rp["price"]
    if tel is not None:
        stats, tstats = stats
    if layout is None:
        key, k_job, k_spot, k_pol, k_pre, k_rt = split_event_keys(
            carry.key, preempt_on, has_route)
    else:
        key = carry.key
    iota_s = jax.lax.iota(jnp.int32, n_slots)
    iota_r = jax.lax.iota(jnp.int32, n_regions)
    slot_region = _slot_region_iota(topo, iota_s)

    budgets_masked = jnp.where(carry.occ, carry.budgets, INF)
    if work is not None and getattr(kernel, "safety_net", False):
        # can't-be-late watchdog (see _engine_event): the panic clock
        # joins the budget race, so a panic is a forced-early defection
        # to on-demand through the existing deadline machinery
        buf = np.float32(getattr(kernel, "slack_buffer", 0.0))
        rem_tot_all = wk_c.oh + jnp.maximum(wk["total_work"] - wk_c.prog,
                                            0.0)
        panic_at = jnp.maximum(
            deadline_slack(wk["deadline"], wk_c.life, rem_tot_all,
                           wk["od_time"], buf), 0.0)
        panic_at = jnp.where(carry.occ, panic_at, INF)
        panic_armed = panic_at < budgets_masked
        budgets_masked = jnp.minimum(budgets_masked, panic_at)
    else:
        panic_armed = None
    deadline = jnp.min(budgets_masked)
    defect_slot = argmin_first(budgets_masked)

    min_job = jnp.min(carry.next_job)
    home = argmin_first(carry.next_job).astype(jnp.int32)
    min_spot = jnp.min(carry.next_spot)
    spot_region = argmin_first(carry.next_spot).astype(jnp.int32)
    if preempt_on:
        if layout is None:
            min_pre = jnp.min(carry.next_preempt)
            pre_region = argmin_first(carry.next_preempt).astype(jnp.int32)
        else:
            min_pre = carry.next_preempt[0]
            pre_region = thinning_pick(
                eff_hazard, layout.uniforms(x, layout.preempt)[1])
        dt = jnp.minimum(jnp.minimum(min_job, min_spot),
                         jnp.minimum(deadline, min_pre))
        is_spot = min_spot <= jnp.minimum(min_job,
                                          jnp.minimum(deadline, min_pre))
        is_pre = (~is_spot) & (min_pre <= jnp.minimum(min_job, deadline))
        is_deadline = (~is_spot) & (~is_pre) & (deadline <= min_job)
        is_job = (~is_spot) & (~is_pre) & (~is_deadline)
    else:
        pre_region = jnp.zeros((), jnp.int32)
        dt = jnp.minimum(jnp.minimum(min_job, min_spot), deadline)
        is_spot = min_spot <= jnp.minimum(min_job, deadline)
        is_pre = jnp.zeros((), jnp.bool_)
        is_deadline = (~is_spot) & (deadline <= min_job)
        is_job = (~is_spot) & (~is_deadline)

    if ep is not None:
        # segment boundary joins the race with highest priority: no queue
        # activity, clocks age by dt, the segment index advances
        is_boundary = env_c.next_boundary <= dt
        dt = jnp.minimum(dt, env_c.next_boundary)
        not_b = ~is_boundary
        is_spot = is_spot & not_b
        is_pre = is_pre & not_b
        is_deadline = is_deadline & not_b
        is_job = is_job & not_b

    ages = carry.ages + dt
    budgets = jnp.where(carry.occ, carry.budgets - dt, INF)

    # ---- job arrival in region `home`: route, then ask the admission law --
    rates = rp["rate"] / rp["spot_scale"]
    if ep is not None:
        rates = rates * avail_row  # rate == 0 marks a blacked-out region
    view = RegionView(
        home=home,
        price=eff_price, hazard=eff_hazard, notice=rp["notice"],
        rate=rates,
        job_rate=rp["job_rate"] / rp["job_scale"],
        qlen_region=carry.qlen,
        free_slots=jnp.maximum(rp["rmax"] - carry.qlen, 0),
    )
    if not has_route:
        target = home
    elif layout is None:
        target = jnp.asarray(kernel.route(params, carry.qlen, view, k_rt),
                             jnp.int32)
    else:
        target = jnp.asarray(
            _kernel_route_slab(kernel, params, carry.qlen, view, layout, x),
            jnp.int32)
    qlen_t = jnp.sum(jnp.where(iota_r == target, carry.qlen, 0))
    rmax_t = jnp.sum(jnp.where(iota_r == target, rp["rmax"], 0))
    if layout is None:
        admit_raw, budget = _kernel_region_admit(kernel, params, qlen_t,
                                                 view, k_pol)
    else:
        admit_raw, budget = _kernel_region_admit_slab(kernel, params, qlen_t,
                                                      view, layout, x)
    admit = is_job & admit_raw & (qlen_t < rmax_t)
    od_now = is_job & (~admit)
    target_mask = slot_region == target
    join_slot = argmin_first(jnp.where(target_mask,
                                     carry.occ.astype(jnp.int32), 2))

    # ---- region spot slot: serve the FIFO-oldest job queued there --------
    eligible_s = carry.occ & (slot_region == spot_region)
    serve_slot = argmin_first(jnp.where(eligible_s, carry.order, _ORDER_MAX))
    has_elig = jnp.any(eligible_s)
    served = is_spot & has_elig
    wait_served = jnp.sum(jnp.where(iota_s == serve_slot, ages, 0.0))
    price_s = eff_price[spot_region]

    if work is not None:
        # one unit of service: overhead debt first, spill into progress;
        # final only when the remaining total clears (see _engine_event)
        serve_vec = served & (iota_s == serve_slot)
        rem_tot = wk_c.oh + (wk["total_work"] - wk_c.prog)
        rem_serve = jnp.sum(jnp.where(iota_s == serve_slot, rem_tot, 0.0))
        oh_new = jnp.where(serve_vec, jnp.maximum(wk_c.oh - 1.0, 0.0),
                           wk_c.oh)
        spill = jnp.maximum(1.0 - wk_c.oh, 0.0)
        prog_new = jnp.where(
            serve_vec, jnp.minimum(wk_c.prog + spill, wk["total_work"]),
            wk_c.prog)
        done_inc = jnp.sum(jnp.where(serve_vec, prog_new - wk_c.prog, 0.0))
        if work.ckpt == "periodic":
            take_vec = (serve_vec & (rem_tot > 1.0)
                        & (prog_new - wk_c.ckpt >= wk["ckpt_period"]))
            ckpt_new = jnp.where(take_vec, prog_new, wk_c.ckpt)
            oh_new = oh_new + jnp.where(take_vec, wk["ckpt_cost"], 0.0)
            ckpt_taken = jnp.any(take_vec)
        else:
            ckpt_new = wk_c.ckpt
            ckpt_taken = jnp.zeros((), jnp.bool_)
        complete_serve = served & (rem_serve <= 1.0)
    else:
        complete_serve = served

    # ---- region preemption: revoke the FIFO-oldest job in that region ----
    if preempt_on:
        eligible_p = carry.occ & (slot_region == pre_region)
        pre_slot = argmin_first(jnp.where(eligible_p, carry.order, _ORDER_MAX))
        pre_hit = is_pre & jnp.any(eligible_p)
        age_pre = jnp.sum(jnp.where(iota_s == pre_slot, ages, 0.0))
        # re-admission sees the region's queue WITHOUT the revoked job (the
        # host orchestrator pops it before consulting the admission law)
        qlen_p = jnp.sum(jnp.where(iota_r == pre_region, carry.qlen, 0))
        qlen_wo = jnp.maximum(qlen_p - 1, 0)
        if layout is None:
            resume_raw = _kernel_on_preempt(kernel, params, age_pre,
                                            rp["notice"][pre_region],
                                            qlen_wo, k_pre)
        else:
            resume_raw = _kernel_on_preempt_slab(kernel, params, age_pre,
                                                 rp["notice"][pre_region],
                                                 qlen_wo, layout, x)
        resume = pre_hit & resume_raw
        defect_pre = pre_hit & (~resume)
        price_p = eff_price[pre_region]
    else:
        pre_slot = jnp.zeros((), jnp.int32)
        pre_hit = jnp.zeros((), jnp.bool_)
        age_pre = jnp.zeros((), jnp.float32)
        resume = jnp.zeros((), jnp.bool_)
        defect_pre = jnp.zeros((), jnp.bool_)
        price_p = jnp.zeros((), jnp.float32)

    if work is not None and preempt_on:
        # rollback (see _market_event): resume restarts from the last
        # checkpoint and owes the restart overhead; notice mode saves
        # current progress iff it fits the firing REGION's notice window
        if work.ckpt == "notice":
            saved = resume & checkpoint_within_notice(
                wk["ckpt_time"], rp["notice"][pre_region])
        else:
            saved = jnp.zeros((), jnp.bool_)
        prog_p = jnp.sum(jnp.where(iota_s == pre_slot, prog_new, 0.0))
        ckpt_p = jnp.sum(jnp.where(iota_s == pre_slot, ckpt_new, 0.0))
        ckpt_val = jnp.where(saved, jnp.maximum(ckpt_p, prog_p), ckpt_p)
        resume_vec = resume & (iota_s == pre_slot)
        prog_new = jnp.where(resume_vec, ckpt_val, prog_new)
        oh_new = jnp.where(resume_vec, wk["restart_overhead"], oh_new)
        ckpt_new = jnp.where(resume_vec, ckpt_val, ckpt_new)
        lost = jnp.where(resume, jnp.maximum(prog_p - ckpt_val, 0.0), 0.0)
        oh_inc = jnp.where(resume, wk["restart_overhead"], 0.0)
        ckpt_taken = ckpt_taken | (resume & saved)
    elif work is not None:
        lost = jnp.zeros((), jnp.float32)
        oh_inc = jnp.zeros((), jnp.float32)

    # ---- deadline: the minimal-budget job defects to on-demand ----
    defected = is_deadline
    age_defect = jnp.sum(jnp.where(iota_s == defect_slot, ages, 0.0))

    leave = complete_serve | defected | defect_pre
    leave_slot = jnp.where(served, serve_slot,
                           jnp.where(defected, defect_slot, pre_slot))
    leave_region = jnp.sum(jnp.where(iota_s == leave_slot, slot_region, 0))

    join_mask = admit & (iota_s == join_slot)
    leave_mask = leave & (iota_s == leave_slot)
    resume_mask = resume & (iota_s == pre_slot)
    ages = jnp.where(join_mask | resume_mask, 0.0, ages)
    budgets = jnp.where(join_mask, budget,
                        jnp.where(resume_mask, INF, budgets))
    occ = (carry.occ | join_mask) & (~leave_mask)
    order = jnp.where(join_mask | resume_mask, carry.next_seq, carry.order)
    if work is not None:
        life_new = jnp.where(join_mask, 0.0, wk_c.life + dt)
        prog_new = jnp.where(join_mask, 0.0, prog_new)
        oh_new = jnp.where(join_mask, 0.0, oh_new)
        ckpt_new = jnp.where(join_mask, 0.0, ckpt_new)

    fire_j = is_job & (iota_r == home)
    fire_s = is_spot & (iota_r == spot_region)
    if layout is None:
        job_draws = _sample_job_clocks(topo, k_job, rp)
        spot_draws = _sample_region_spot_clocks(topo, k_spot, rp)
    else:
        job_draws = _slab_spot_clocks(tuple(r.job for r in topo.regions),
                                      layout.uniforms(x, layout.job),
                                      rp["job_scale"])
        spot_draws = _slab_spot_clocks(tuple(r.spot for r in topo.regions),
                                       layout.uniforms(x, layout.spot),
                                       rp["spot_scale"])
    if ep is not None:
        # availability scales fresh supply draws; on a boundary, survived
        # spot clocks are rescaled by the availability ratio and survived
        # hazard clocks by the hazard ratio — exact by memorylessness.
        # Demand (job) clocks are deliberately untouched.
        seg_new = seg + is_boundary.astype(jnp.int32)
        inv_old = inv_avail(avail_row)
        inv_new = inv_avail(env_row(ep["avail"], seg_new))
        eff_hazard_new = rp["hazard"] * env_row(ep["hazard"], seg_new)
        spot_draws = spot_draws * inv_new
    else:
        eff_hazard_new = rp["hazard"]
    next_job = jnp.where(fire_j, job_draws, carry.next_job - dt)
    next_spot = jnp.where(fire_s, spot_draws, carry.next_spot - dt)
    if ep is not None:
        next_spot = jnp.where(is_boundary, next_spot * (inv_new / inv_old),
                              next_spot)
    if not preempt_on:
        next_preempt = carry.next_preempt
    elif layout is None:
        fire_p = is_pre & (iota_r == pre_region)
        next_preempt = jnp.where(
            fire_p, sample_hazard_clocks(_region_tags(topo), k_pre,
                                         eff_hazard_new),
            carry.next_preempt - dt)
        if ep is not None:
            next_preempt = jnp.where(
                is_boundary,
                next_preempt * clock_rescale(eff_hazard, eff_hazard_new),
                next_preempt)
    else:
        # superposed scalar clock (see _market_event)
        next_preempt = jnp.where(
            is_pre, hazard_clock(eff_hazard_new,
                                 layout.uniforms(x, layout.preempt)[0]),
            carry.next_preempt - dt)
        if ep is not None:
            next_preempt = jnp.where(
                is_boundary,
                next_preempt * clock_rescale(jnp.sum(eff_hazard),
                                             jnp.sum(eff_hazard_new)),
                next_preempt)

    new_carry = RegionState(
        key=key,
        next_job=next_job,
        next_spot=next_spot,
        next_preempt=next_preempt,
        ages=ages,
        budgets=budgets,
        occ=occ,
        order=order,
        next_seq=carry.next_seq + jnp.where(admit | resume, 1, 0),
        qlen=(carry.qlen
              + jnp.where(admit & (iota_r == target), 1, 0)
              - jnp.where(leave & (iota_r == leave_region), 1, 0)),
    )
    completed = od_now | served | defected | defect_pre | resume
    new_stats = RegionWindowStats(
        jobs_arrived=stats.jobs_arrived + is_job.astype(jnp.int32),
        jobs_completed=stats.jobs_completed + completed.astype(jnp.int32),
        spot_served=stats.spot_served + served.astype(jnp.int32),
        ondemand=stats.ondemand
        + (od_now | defected | defect_pre).astype(jnp.int32),
        cost_sum=stats.cost_sum
        + jnp.where(served, price_s, 0.0)
        + jnp.where(od_now | defected | defect_pre, k_cost, 0.0)
        + jnp.where(pre_hit, price_p, 0.0),
        delay_sum=stats.delay_sum
        + jnp.where(served, wait_served, 0.0)
        + jnp.where(defected, age_defect, 0.0)
        + jnp.where(pre_hit, age_pre, 0.0),
        time_elapsed=stats.time_elapsed + dt,
        empty_time=stats.empty_time
        + jnp.where(jnp.sum(carry.qlen) == 0, dt, 0.0),
        spot_arrivals=stats.spot_arrivals + is_spot.astype(jnp.int32),
        spot_found_empty=stats.spot_found_empty
        + (is_spot & (~has_elig)).astype(jnp.int32),
        resumed=stats.resumed + resume.astype(jnp.int32),
        spot_cost=stats.spot_cost
        + jnp.where(served, price_s, 0.0)
        + jnp.where(pre_hit, price_p, 0.0),
        routed_home=stats.routed_home
        + (admit & (target == home)).astype(jnp.int32),
        region_served=stats.region_served
        + (fire_s & served).astype(jnp.int32),
        region_spot_arrivals=stats.region_spot_arrivals
        + fire_s.astype(jnp.int32),
        region_preempted=stats.region_preempted
        + (pre_hit & (iota_r == pre_region)).astype(jnp.int32),
        region_jobs=stats.region_jobs + fire_j.astype(jnp.int32),
        region_routed=stats.region_routed
        + (admit & (iota_r == target)).astype(jnp.int32),
    )
    if tel is not None:
        defect_region = jnp.sum(jnp.where(iota_s == defect_slot,
                                          slot_region, 0))
        loc = jnp.where(is_spot, spot_region,
                        jnp.where(is_pre, pre_region,
                                  jnp.where(is_deadline, defect_region,
                                            target)))
        tstats = telemetry_update(
            tel, tstats, t=new_stats.time_elapsed, is_job=is_job,
            is_spot=is_spot, is_pre=is_pre, is_deadline=is_deadline,
            served=served, resume=resume, defected=defected, od_now=od_now,
            wait_sample=jnp.where(served, wait_served,
                                  jnp.where(defected, age_defect, age_pre)),
            wait_valid=served | defected | pre_hit,
            cost_inc=jnp.where(served, price_s, 0.0)
            + jnp.where(od_now | defected | defect_pre, k_cost, 0.0)
            + jnp.where(pre_hit, price_p, 0.0),
            cost_valid=served | od_now | defected | pre_hit,
            loc=loc, n_locs=n_regions, qlen=jnp.sum(new_carry.qlen))
        out_stats = (new_stats, tstats)
    else:
        out_stats = new_stats
    out_carry = new_carry
    if ep is not None:
        estats = env_update(
            estats, is_boundary=is_boundary,
            kind_prev=env_row(ep["kind"], seg),
            kind_next=env_row(ep["kind"], seg_new), dt=dt, is_job=is_job,
            od_now=od_now, served=served, resumed=resume)
        new_env = EnvState(
            next_boundary=jnp.where(
                is_boundary,
                env_row(ep["t_end"], seg_new) - env_row(ep["t_end"], seg),
                env_c.next_boundary - dt),
            seg=seg_new)
        out_carry = (new_carry, new_env)
        out_stats = (out_stats, estats)
    if work is not None:
        life_def = jnp.sum(jnp.where(iota_s == defect_slot, wk_c.life + dt,
                                     0.0))
        rem_def = jnp.sum(jnp.where(iota_s == defect_slot, rem_tot, 0.0))
        life_pre = jnp.sum(jnp.where(iota_s == pre_slot, wk_c.life + dt,
                                     0.0))
        rem_pre = jnp.sum(jnp.where(iota_s == pre_slot, rem_tot, 0.0))
        life_srv = jnp.sum(jnp.where(iota_s == serve_slot, wk_c.life + dt,
                                     0.0))
        od = wk["od_time"]
        # finish/miss accounting exactly as in _market_event (live
        # migration: a preempted defector's od remainder is its
        # PRE-rollback remaining total)
        miss = ((od_now & (wk["total_work"] * od > wk["deadline"]))
                | (defected & (life_def + rem_def * od > wk["deadline"]))
                | (defect_pre & (life_pre + rem_pre * od > wk["deadline"]))
                | (complete_serve & (life_srv > wk["deadline"])))
        panic = (defected & jnp.any((iota_s == defect_slot) & panic_armed)
                 if panic_armed is not None else jnp.zeros((), jnp.bool_))
        wstats = survival_update(
            wstats, admitted=is_job,
            finished=od_now | complete_serve | defected | defect_pre,
            missed=miss, checkpoint=ckpt_taken, panic=panic,
            work_done=done_inc, work_lost=lost,
            work_recomputed=lost + oh_inc, overhead_paid=oh_inc)
        return (out_carry, WorkState(prog=prog_new, oh=oh_new,
                                     ckpt=ckpt_new, life=life_new)), \
            (out_stats, wstats)
    return out_carry, out_stats


def _region_layout(topo: RegionTopology, kernel,
                   preempt_on: bool) -> SlabLayout:
    """Slab column map for the region loop: job/spot spans are the max
    ``u_dim`` across regions (shared uniforms, see
    :func:`_slab_spot_clocks`)."""
    return build_slab_layout(
        kernel, job_udim=max(process_udim(r.job) for r in topo.regions),
        spot_udim=max(process_udim(r.spot) for r in topo.regions),
        n=topo.n_regions, preempt_on=preempt_on,
        has_route=hasattr(kernel, "route"), market=True)


def run_region_window(topo: RegionTopology, kernel, preempt_on: bool,
                      state: RegionState, params, rp: dict,
                      k_cost: jax.Array, n_events: int,
                      layout: SlabLayout | None = None,
                      tel: Telemetry | None = None, ep: dict | None = None,
                      work: WorkModel | None = None, wk: dict | None = None
                      ) -> tuple[RegionState, RegionWindowStats]:
    """Run ``n_events`` merged region events; one window of float32 sums."""
    step = functools.partial(_region_event, topo, kernel, preempt_on, layout,
                             params=params, rp=rp, k_cost=k_cost, tel=tel,
                             ep=ep, work=work, wk=wk)
    zeros = _with_zeros(RegionWindowStats.zeros(topo.n_regions), tel,
                        topo.n_regions, env=ep is not None,
                        work=work is not None)
    if layout is None:
        return _scan_window(step, zeros, state, n_events)
    return _scan_window_slab(lambda c, s, x: step(c, s, x=x), zeros, state,
                             n_events, layout.n_cols,
                             paired=(ep is not None) or (work is not None))


def run_region_chunked(topo: RegionTopology, kernel, preempt_on: bool,
                       state: RegionState, params, rp: dict,
                       k_cost: jax.Array, n_events: int, chunk_events: int,
                       layout: SlabLayout | None = None,
                       tel: Telemetry | None = None, ep: dict | None = None,
                       work: WorkModel | None = None, wk: dict | None = None
                       ) -> tuple[RegionState, RegionWindowStats]:
    step = functools.partial(_region_event, topo, kernel, preempt_on, layout,
                             params=params, rp=rp, k_cost=k_cost, tel=tel,
                             ep=ep, work=work, wk=wk)
    zeros = _with_zeros(RegionWindowStats.zeros(topo.n_regions), tel,
                        topo.n_regions, env=ep is not None,
                        work=work is not None)
    rebase = _rebase_for(ep, work)
    if layout is None:
        return _scan_chunked(step, zeros, state, n_events, chunk_events,
                             rebase=rebase)
    return _scan_chunked_slab(lambda c, s, x: step(c, s, x=x), zeros, state,
                              n_events, chunk_events, layout.n_cols,
                              paired=(ep is not None) or (work is not None),
                              rebase=rebase)


@functools.partial(
    jax.jit,
    static_argnames=("topo", "kernel", "preempt_on", "n_events",
                     "chunk_events", "burn_in", "rng", "tel", "work"),
)
def _run_region_sim_jit(topo, kernel, preempt_on, n_events, chunk_events,
                        burn_in, rng, params, rp, k_cost, key, tel=None,
                        ep=None, work=None, wk=None):
    layout = (_region_layout(topo, kernel, preempt_on)
              if rng == "slab" else None)
    state = init_region_state(key, topo, rp, preempt_on,
                              scalar_preempt=layout is not None, ep=ep)
    if ep is not None:
        state = (state, init_env_state(ep))
    if work is not None:
        state = (state, init_work_state(topo.total_slots))
    if burn_in:
        state, _ = run_region_window(topo, kernel, preempt_on, state, params,
                                     rp, k_cost, burn_in, layout=layout,
                                     tel=tel, ep=ep, work=work, wk=wk)
        state = _rebase_for(ep, work)(state)
    return run_region_chunked(topo, kernel, preempt_on, state, params, rp,
                              k_cost, n_events, chunk_events, layout=layout,
                              tel=tel, ep=ep, work=work, wk=wk)


@functools.partial(
    jax.jit,
    static_argnames=("topo", "kernel", "preempt_on", "n_events",
                     "chunk_events", "burn_in", "rng", "tel", "work"),
)
def _run_region_sweep_jit(topo, kernel, preempt_on, n_events, chunk_events,
                          burn_in, rng, params, rp, k_cost, keys, tel=None,
                          ep=None, work=None, wk=None):
    """(grid × regions-config × seeds) fleet as one nested-vmap XLA program
    (broadcast ``in_axes``; see :func:`_flat_lane_args`)."""
    layout = (_region_layout(topo, kernel, preempt_on)
              if rng == "slab" else None)

    def one(p, r, kc, key):
        state = init_region_state(key, topo, r, preempt_on,
                                  scalar_preempt=layout is not None, ep=ep)
        if ep is not None:
            state = (state, init_env_state(ep))
        if work is not None:
            state = (state, init_work_state(topo.total_slots))
        if burn_in:
            state, _ = run_region_window(topo, kernel, preempt_on, state, p,
                                         r, kc, burn_in, layout=layout,
                                         tel=tel, ep=ep, work=work, wk=wk)
            state = _rebase_for(ep, work)(state)
        _, stats = run_region_chunked(topo, kernel, preempt_on, state, p, r,
                                      kc, n_events, chunk_events,
                                      layout=layout, tel=tel, ep=ep,
                                      work=work, wk=wk)
        return stats

    per_seeds = jax.vmap(one, in_axes=(None, None, None, 0))
    return jax.vmap(per_seeds, in_axes=(0, 0, 0, None))(params, rp, k_cost,
                                                        keys)


@functools.partial(
    jax.jit,
    static_argnames=("topo", "kernel", "preempt_on", "n_events",
                     "chunk_events", "burn_in", "tile", "interpret",
                     "executor", "rng", "tel", "work"),
)
def _run_region_sweep_pallas_jit(topo, kernel, preempt_on, n_events,
                                 chunk_events, burn_in, tile, interpret,
                                 params, rp, k_cost, keys,
                                 executor="pallas", rng="split", tel=None,
                                 ep=None, work=None, wk=None):
    """The region fleet through the same batched-event kernel family: the
    engine-state blocks grow a region axis — (tile, R) clock vectors,
    (tile, sum rmax_r) packed slot arrays — and :func:`_region_event` is
    the vmap-ed kernel body.  Bit-for-bit the ``executor="ref"`` scan
    oracle; integer stats bitwise / float sums to ~ulp vs
    :func:`_run_region_sweep_jit` (see the module docstring).  Under
    ``rng="slab"`` the slab is a per-window input block and the kernel
    performs no RNG at all."""
    g, s = k_cost.shape[0], keys.shape[0]
    (params_f, rp_f), k_f, keys_f = _flat_lane_args((params, rp), k_cost,
                                                    keys)
    params_b = {"params": params_f, "rp": rp_f, "k": k_f}
    layout = (_region_layout(topo, kernel, preempt_on)
              if rng == "slab" else None)
    state0 = jax.vmap(
        lambda key, r: init_region_state(
            key, topo, r, preempt_on,
            scalar_preempt=layout is not None, ep=ep))(keys_f, rp_f)
    plan = _window_plan(n_events, chunk_events, burn_in)

    if layout is not None:
        slab = _lane_slabs(state0, plan, layout,
                           compiled=executor == "pallas" and not interpret)
    else:
        slab = None
    if ep is not None:
        params_b["ep"], es0 = _env_lane_blocks(ep, keys_f.shape[0])
        state0 = (state0, es0)
    if work is not None:
        params_b["wk"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (keys_f.shape[0],)), wk)
        state0 = (state0, init_work_state(topo.total_slots,
                                          keys_f.shape[0]))

    if layout is not None:
        def step(carry, stats, p, x):
            return _region_event(topo, kernel, preempt_on, layout, carry,
                                 stats, p["params"], p["rp"], p["k"], x=x,
                                 tel=tel, ep=p.get("ep"), work=work,
                                 wk=p.get("wk"))
    else:
        def step(carry, stats, p):
            return _region_event(topo, kernel, preempt_on, None, carry,
                                 stats, p["params"], p["rp"], p["k"],
                                 tel=tel, ep=p.get("ep"), work=work,
                                 wk=p.get("wk"))

    zeros = _with_zeros(RegionWindowStats.zeros(topo.n_regions), tel,
                        topo.n_regions, env=ep is not None,
                        work=work is not None)
    epilogue = _rebase_for(ep, work)
    if executor == "ref":
        _, stats = batched_event_windows_ref(
            step, state0, params_b, zeros, plan, slab=slab,
            epilogue=epilogue)
    else:
        _, stats = batched_events(
            step, state0, params_b, zeros, plan, slab=slab, tile=tile,
            interpret=interpret, epilogue=epilogue)
    if burn_in:
        stats = jax.tree.map(lambda x: x[:, 1:], stats)
    return _unflatten_lanes(stats, g, s)


def _region_sweep_lanes(topo, kernel, preempt_on, n_events, chunk_events,
                        burn_in, tile, interpret, params_f, rp_f, k_f,
                        keys_f, *, executor, rng, tel=None, ep=None,
                        work=None, wk=None):
    """One shard of flat region lanes through any executor (cf.
    :func:`_sweep_lanes`; the regions-config tree ``rp_f`` is a per-lane
    grid axis exactly as in :func:`_run_region_sweep_pallas_jit`)."""
    layout = (_region_layout(topo, kernel, preempt_on)
              if rng == "slab" else None)
    if executor == "xla":
        def one(p, r, kc, key):
            state = init_region_state(key, topo, r, preempt_on,
                                      scalar_preempt=layout is not None,
                                      ep=ep)
            if ep is not None:
                state = (state, init_env_state(ep))
            if work is not None:
                state = (state, init_work_state(topo.total_slots))
            if burn_in:
                state, _ = run_region_window(topo, kernel, preempt_on, state,
                                             p, r, kc, burn_in, layout=layout,
                                             tel=tel, ep=ep, work=work,
                                             wk=wk)
                state = _rebase_for(ep, work)(state)
            _, stats = run_region_chunked(topo, kernel, preempt_on, state, p,
                                          r, kc, n_events, chunk_events,
                                          layout=layout, tel=tel, ep=ep,
                                          work=work, wk=wk)
            return stats

        return jax.vmap(one)(params_f, rp_f, k_f, keys_f)

    params_b = {"params": params_f, "rp": rp_f, "k": k_f}
    state0 = jax.vmap(
        lambda key, r: init_region_state(
            key, topo, r, preempt_on,
            scalar_preempt=layout is not None, ep=ep))(keys_f, rp_f)
    plan = _window_plan(n_events, chunk_events, burn_in)
    slab = None if layout is None else _lane_slabs(
        state0, plan, layout, compiled=executor == "pallas" and not interpret)
    if ep is not None:
        params_b["ep"], es0 = _env_lane_blocks(ep, keys_f.shape[0])
        state0 = (state0, es0)
    if work is not None:
        params_b["wk"] = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (keys_f.shape[0],)), wk)
        state0 = (state0, init_work_state(topo.total_slots,
                                          keys_f.shape[0]))

    if layout is not None:
        def step(carry, stats, p, x):
            return _region_event(topo, kernel, preempt_on, layout, carry,
                                 stats, p["params"], p["rp"], p["k"], x=x,
                                 tel=tel, ep=p.get("ep"), work=work,
                                 wk=p.get("wk"))
    else:
        def step(carry, stats, p):
            return _region_event(topo, kernel, preempt_on, None, carry,
                                 stats, p["params"], p["rp"], p["k"],
                                 tel=tel, ep=p.get("ep"), work=work,
                                 wk=p.get("wk"))

    zeros = _with_zeros(RegionWindowStats.zeros(topo.n_regions), tel,
                        topo.n_regions, env=ep is not None,
                        work=work is not None)
    epilogue = _rebase_for(ep, work)
    if executor == "ref":
        _, stats = batched_event_windows_ref(
            step, state0, params_b, zeros, plan, slab=slab, epilogue=epilogue)
    else:
        _, stats = batched_events(
            step, state0, params_b, zeros, plan, slab=slab, tile=tile,
            interpret=interpret, epilogue=epilogue)
    if burn_in:
        stats = jax.tree.map(lambda x: x[:, 1:], stats)
    return stats


@functools.partial(
    jax.jit,
    static_argnames=("topo", "kernel", "preempt_on", "n_events",
                     "chunk_events", "burn_in", "tile", "interpret", "mesh",
                     "executor", "rng", "tel", "work"),
)
def _run_region_sweep_sharded_jit(topo, kernel, preempt_on, n_events,
                                  chunk_events, burn_in, tile, interpret,
                                  mesh, params, rp, k_cost, keys,
                                  executor="xla", rng="split", tel=None,
                                  ep=None, work=None, wk=None):
    """The region fleet lane-partitioned across a 1-D device mesh (cf.
    :func:`_run_sweep_sharded_jit`)."""
    g, s = k_cost.shape[0], keys.shape[0]
    (params_f, rp_f), k_f, keys_f = _flat_lane_args((params, rp), k_cost,
                                                    keys)
    lanes = g * s
    params_f, rp_f, k_f, keys_f = pad_lanes((params_f, rp_f, k_f, keys_f),
                                            _pad_count(lanes, mesh))
    spec, rspec = lane_spec(mesh), jax.sharding.PartitionSpec()

    def local(pf, rf, kf, keysf, ep_, wk_):
        return _region_sweep_lanes(topo, kernel, preempt_on, n_events,
                                   chunk_events, burn_in, tile, interpret,
                                   pf, rf, kf, keysf, executor=executor,
                                   rng=rng, tel=tel, ep=ep_, work=work,
                                   wk=wk_)

    stats = shard_map_1d(local, mesh=mesh,
                         in_specs=(spec, spec, spec, spec, rspec, rspec),
                         out_specs=spec)(params_f, rp_f, k_f, keys_f, ep, wk)
    if lanes != keys_f.shape[0]:
        stats = jax.tree.map(lambda x: x[:lanes], stats)
    return _unflatten_lanes(stats, g, s)


def summarize_region(stats: RegionWindowStats,
                     telemetry: Telemetry | None = None,
                     env: EnvTimeline | None = None,
                     work: WorkModel | None = None) -> dict:
    """Float64 chunk reduction + region-specific derived statistics.

    Extends :func:`summarize`'s dict with preemption counters, spot spend,
    per-job statistics (leg vs job accounting as in
    :func:`summarize_market`), per-region served/arrival/utilization
    arrays (trailing region axis), and the routing flow:
    ``region_jobs`` (arrivals by home region), ``region_routed``
    (admissions by target region), and ``cross_region_frac`` (the fraction
    of admitted jobs the routing hook sent away from home).  With
    ``telemetry``, ``stats`` is the ``(base, telemetry)`` pair and the
    telemetry fields are appended (base keys unchanged; :func:`summarize`).
    With ``env``, the env block rides outermost and the shock counters are
    appended.  With ``work``, the survival ledger rides outermost of all
    and its job-level counters are appended (:func:`summarize_survival`).
    """
    wstats = None
    if work is not None:
        stats, wstats = stats
    estats = None
    if env is not None:
        stats, estats = stats
    tstats = None
    if telemetry is not None:
        stats, tstats = stats
    n_common = len(WindowStats._fields)
    out = summarize(WindowStats(*stats[:n_common]))

    def _red(name):
        x = getattr(stats, name)
        axis = -2 if name in _REGION_FIELDS else -1
        return np.asarray(x, np.float64).sum(axis=axis)

    resumed = _red("resumed")
    spot_cost = _red("spot_cost")
    routed_home = _red("routed_home")
    region_served = _red("region_served")
    region_arrivals = _red("region_spot_arrivals")
    region_preempted = _red("region_preempted")
    region_jobs = _red("region_jobs")
    region_routed = _red("region_routed")
    cost_sum = _red("cost_sum")
    delay_sum = _red("delay_sum")
    final = np.maximum(_red("spot_served") + _red("ondemand"), 1.0)
    admitted = region_routed.sum(axis=-1)
    cross = np.where(admitted > 0,
                     1.0 - routed_home / np.maximum(admitted, 1.0), 0.0)
    out.update({
        "preemptions": region_preempted.sum(axis=-1),
        "resumed": resumed,
        "spot_cost": spot_cost,
        "avg_cost_job": cost_sum / final,
        "avg_delay_job": delay_sum / final,
        "routed_home": routed_home,
        "cross_region_frac": cross,
        "region_served": region_served,
        "region_spot_arrivals": region_arrivals,
        "region_preempted": region_preempted,
        "region_jobs": region_jobs,
        "region_routed": region_routed,
        "region_utilization": region_served / np.maximum(region_arrivals,
                                                         1.0),
    })
    if telemetry is not None:
        out = _merge_telemetry(out, telemetry, tstats, stats.time_elapsed)
    if estats is not None:
        out.update(summarize_env(estats))
    if wstats is not None:
        out.update(summarize_survival(wstats))
    return out


def run_region_sim(
    topology: RegionTopology,
    kernel,
    params=None,
    *,
    k: float = 10.0,
    n_events: int,
    key: jax.Array,
    burn_in: int = 0,
    chunk_events: int | None = DEFAULT_CHUNK_EVENTS,
    impl: str = "xla",
    rng: str = "split",
    tile: int = 256,
    interpret: bool | None = None,
    telemetry: Telemetry | None = None,
    env: EnvTimeline | None = None,
    work: WorkModel | None = None,
) -> dict:
    """Run one routing policy on one topology point; scalar long-run stats.

    A degenerate topology (:attr:`RegionTopology.is_degenerate`) with a
    non-routing kernel reproduces :func:`run_sim` (and the 1-pool
    :func:`run_market_sim`) bit-for-bit per seed.  ``chunk_events`` /
    ``impl`` / ``rng`` behave exactly as in :func:`run_sim`; ``env``
    attaches an :class:`~repro.core.env.EnvTimeline` (per-region price /
    hazard / availability segments) exactly as in :func:`run_sim`;
    ``work`` attaches the work structure and survival ledger exactly as
    in :func:`run_market_sim`.
    """
    with EntrySpan(f"repro.run_region_sim[{impl}]") as call:
        topology = as_topology(topology)
        params = {} if params is None else params
        _check_rng(rng)
        _check_telemetry(telemetry)
        _check_env(env)
        _check_work(work, kernel)
        _check_run_shape("run_region_sim", n_events, burn_in)
        interp = _resolve_interpret("run_region_sim", impl, rng, interpret)
        rp = topology.params()
        ep = _env_params(env, topology.n_regions)
        wk = None if work is None else work.params()
        chunk = (n_events if chunk_events is None
                 else min(chunk_events, n_events))
        call.phase("dispatch")
        if impl in ("pallas", "ref"):
            stats = _run_region_sweep_pallas_jit(
                topology, kernel, topology.preemptible, n_events, chunk,
                burn_in, tile,
                interp,
                jax.tree.map(lambda x: jnp.asarray(x)[None], params),
                jax.tree.map(lambda x: jnp.asarray(x)[None], rp),
                jnp.float32(k)[None], _raw_keys(key)[None], executor=impl,
                rng=rng, tel=telemetry, ep=ep, work=work, wk=wk)
            stats = jax.tree.map(lambda x: x[0, 0], stats)
        elif impl == "xla":
            _, stats = _run_region_sim_jit(topology, kernel,
                                           topology.preemptible, n_events,
                                           chunk, burn_in, rng, params, rp,
                                           jnp.float32(k), key,
                                           tel=telemetry, ep=ep, work=work,
                                           wk=wk)
        else:
            raise ValueError(
                f"unknown impl {impl!r} (expected 'xla'|'pallas'|'ref')")
        stats = call.to_host(stats)
        return {name: _scalar_or_array(v)
                for name, v in summarize_region(stats, telemetry, env=env,
                                                work=work).items()}


def run_region_sweep(
    topology: RegionTopology,
    kernel,
    params=None,
    *,
    k: float | np.ndarray | jax.Array = 10.0,
    vector_params=None,
    prices=None,
    hazards=None,
    notices=None,
    spot_scales=None,
    job_scales=None,
    n_events: int,
    key: jax.Array,
    n_seeds: int = 1,
    burn_in: int = 0,
    chunk_events: int | None = DEFAULT_CHUNK_EVENTS,
    impl: str = "xla",
    rng: str = "split",
    tile: int = 256,
    interpret: bool | None = None,
    telemetry: Telemetry | None = None,
    env: EnvTimeline | None = None,
    work: WorkModel | None = None,
    shard: str = "none",
    mesh=None,
) -> dict:
    """Run a (params × k × regions-config × seeds) grid as ONE jitted call.

    ``params`` leaves and ``k`` broadcast to a common grid shape exactly as
    in :func:`run_sweep`.  ``vector_params`` is a dict of *vector-valued*
    kernel parameters whose LAST axis is carried into every grid point
    instead of being swept: an ``(m,)`` leaf fixes one vector for the whole
    grid, a ``grid_shape + (m,)`` leaf sweeps the vector itself (e.g.
    ``{"region_logits": logits}`` for ``choice="weighted"`` routing — the
    logits stay ``(R,)`` per point while ``r`` sweeps).  ``prices``/
    ``hazards``/``notices``/
    ``spot_scales``/``job_scales`` optionally override the topology's
    static region configuration per grid point: a scalar applies to every
    region, an ``(R,)`` vector fixes one config, and a ``grid_shape + (R,)``
    array sweeps the region configuration inside the same compiled program
    (the regions-config axis of the grid — ``job_scales`` sweeps *demand*
    per region, the axis the market engine does not have).

    ``impl``/``tile``/``interpret`` select the executor exactly as in
    :func:`run_sweep`; the Pallas path widens the VMEM-resident state tile
    with the (tile, R) clock vectors and the (tile, sum rmax_r) packed slot
    partition — bit-for-bit the ``"ref"`` oracle, integer stats bitwise /
    float sums to ~ulp vs ``"xla"`` (the module docstring's executor
    contract).  ``shard="lanes"`` partitions the flattened lane axis
    across a 1-D device mesh exactly as in :func:`run_sweep`
    (regions-config and vector-param lanes ride along).

    Returns :func:`summarize_region`'s dict; scalar statistics are shaped
    ``grid_shape + (n_seeds,)`` and per-region statistics
    ``grid_shape + (n_seeds, R)``.
    """
    with EntrySpan(f"repro.run_region_sweep[{impl}]") as call:
        topology = as_topology(topology)
        n = topology.n_regions
        params = {} if params is None else params
        _check_rng(rng)
        _check_telemetry(telemetry)
        _check_env(env)
        _check_work(work, kernel)
        _check_shard("run_region_sweep", shard, mesh)
        _check_run_shape("run_region_sweep", n_events, burn_in)
        interp = _resolve_interpret("run_region_sweep", impl, rng, interpret)
        _check_loc_overrides("run_region_sweep", n, "region", prices=prices,
                             hazards=hazards, notices=notices,
                             spot_scales=spot_scales, job_scales=job_scales)
        ep = _env_params(env, n)
        wk = None if work is None else work.params()
        params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)
        vparams = {} if vector_params is None else jax.tree.map(
            lambda x: jnp.asarray(x, jnp.float32), dict(vector_params))
        if vparams and not isinstance(params, dict):
            raise TypeError("vector_params requires params to be a dict")
        k = jnp.asarray(k, jnp.float32)
        overrides = {"price": prices, "hazard": hazards, "notice": notices,
                     "spot_scale": spot_scales, "job_scale": job_scales}
        override_shapes = [jnp.asarray(v).shape[:-1]
                           for v in overrides.values()
                           if v is not None and jnp.asarray(v).ndim > 1]
        grid_shape = jnp.broadcast_shapes(
            k.shape, *(x.shape for x in jax.tree.leaves(params)),
            *(x.shape[:-1] for x in jax.tree.leaves(vparams)),
            *override_shapes,
        )
        flat = lambda x: jnp.broadcast_to(x, grid_shape).reshape(-1)
        vflat = lambda x: jnp.broadcast_to(
            x, grid_shape + x.shape[-1:]).reshape((-1,) + x.shape[-1:])
        params_flat = {**jax.tree.map(flat, params),
                       **jax.tree.map(vflat, vparams)} if vparams \
            else jax.tree.map(flat, params)
        k_flat = flat(k)
        rp_flat = _broadcast_config_params(n, topology.params(), overrides,
                                           grid_shape)
        preempt_on = topology.preemptible or hazards is not None
        keys = jax.random.split(key, n_seeds)
        chunk = (n_events if chunk_events is None
                 else min(chunk_events, n_events))
        call.phase("dispatch")
        if shard == "lanes":
            if impl not in ("xla", "pallas", "ref"):
                raise ValueError(
                    f"unknown impl {impl!r} (expected 'xla'|'pallas'|'ref')")
            stats = _run_region_sweep_sharded_jit(
                topology, kernel, preempt_on, n_events, chunk, burn_in, tile,
                interp,
                lane_mesh() if mesh is None else mesh, params_flat, rp_flat,
                k_flat, _raw_keys(keys), executor=impl, rng=rng,
                tel=telemetry, ep=ep, work=work, wk=wk)
        elif impl in ("pallas", "ref"):
            stats = _run_region_sweep_pallas_jit(
                topology, kernel, preempt_on, n_events, chunk, burn_in, tile,
                interp,
                params_flat, rp_flat, k_flat, _raw_keys(keys), executor=impl,
                rng=rng, tel=telemetry, ep=ep, work=work, wk=wk)
        elif impl == "xla":
            stats = _run_region_sweep_jit(topology, kernel, preempt_on,
                                          n_events, chunk, burn_in, rng,
                                          params_flat, rp_flat, k_flat, keys,
                                          tel=telemetry, ep=ep, work=work,
                                          wk=wk)
        else:
            raise ValueError(
                f"unknown impl {impl!r} (expected 'xla'|'pallas'|'ref')")
        stats = call.to_host(stats)
        out = summarize_region(stats, telemetry, env=env, work=work)
        return _reshape_sweep(out, grid_shape, n_seeds)
