"""Shared clock + PRNG machinery for the engine's three event loops.

Two randomness streams, one module (PR 5):

``rng="split"`` — the frozen PR-1..4 stream.  Every event splits the lane
key into a 4/5/6-way ladder (job / spot / policy / preempt? / route?) and
every clock-vector refresh folds a per-pool/per-region tag into its subkey
before sampling.  The market and region loops used to carry near-identical
copies of that plumbing (``_pool_spot_keys`` / ``_region_fold_keys``, the
tag-folded preempt-clock refresh, the split ladders); the one copy lives
here now (:func:`split_event_keys`, :func:`tagged_keys`,
:func:`sample_clock_vector`, :func:`sample_hazard_clocks`) and stays
bit-for-bit the PR-4 stream — the seed-compat wrappers and every frozen
degenerate-ledger test run on it unchanged.

``rng="slab"`` — the fast stream.  Profiling the loops shows per-event
PRNG *key arithmetic* (threefry ladders + per-pool ``fold_in`` +
``exponential``), not policy logic, dominates: a 4-region preemptible event
costs ~25 threefry invocations.  The slab stream deletes all of it from the
event body:

  * One counter-based :func:`jax.random.bits` call generates a
    ``(window_events, n_cols)`` uint32 **slab** per float32 window
    (:func:`window_slab`); the event body consumes draws by *static column
    index* (:class:`SlabLayout`), converting raw bits to uniforms /
    exponentials with plain arithmetic (:func:`u01`, :func:`exp_from_u`).
    In the Pallas executor the slab arrives as a plain VMEM input block per
    window — zero in-kernel key arithmetic.
  * The per-pool/per-region Poisson preemption clocks collapse to ONE
    scalar clock at the *superposed* total hazard: the minimum of
    independent ``Exp(h_p)`` clocks is ``Exp(Σ h_p)`` and (by
    memorylessness) the firing pool is an independent categorical draw with
    weights ``h_p`` — :func:`hazard_clock` + :func:`thinning_pick` are that
    law, *exactly* the per-pool vector-clock process (see EXPERIMENTS.md
    §"Event-loop RNG" for the proof sketch and the draw-column table).

Slab-vs-split equivalence is **distributional** (the slab stream holds the
pallas == ref == xla bitwise integer ledger on its own terms; KS tests pin
the slab-vs-split marginals — tests/test_event_rng.py); the split stream
keeps its frozen bitwise contracts.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

_INF = np.float32(3e38)  # np scalar: inlines as a literal in kernel traces

#: uint32 slab columns reserved when a kernel hook is *not* slab-aware: two
#: raw key words are synthesized into a legacy PRNG key (:func:`synth_key`)
#: and the unchanged key-based hook is called.
KEY_SYNTH_COLS = 2


# ---------------------------------------------------------------------------
# Split-mode plumbing (the frozen PR-1..4 stream), deduplicated
# ---------------------------------------------------------------------------


def split_event_keys(key, preempt_on: bool = False, has_route: bool = False):
    """The per-event split ladder, one copy for all three loops.

    Returns ``(key, k_job, k_spot, k_pol, k_pre, k_rt)`` with ``k_pre`` /
    ``k_rt`` present only when their static flag is set (``None``
    otherwise).  The ladder width and subkey order (policy, then preempt,
    then route) are exactly the PR-2/PR-4 layouts, so every frozen
    bit-for-bit contract is preserved.
    """
    n = 4 + int(preempt_on) + int(has_route)
    ks = jax.random.split(key, n)
    k_pre = ks[4] if preempt_on else None
    k_rt = ks[4 + int(preempt_on)] if has_route else None
    return ks[0], ks[1], ks[2], ks[3], k_pre, k_rt


def tagged_keys(tags: tuple, k: jax.Array) -> list:
    """Per-tag sampling keys, label-independent via ``fold_in(k, tag)``.

    A single tag uses ``k`` directly — the PR-1 key layout — so the
    degenerate 1-pool/1-region engines stay bit-for-bit the PR-1 engine.
    Shared by the market (pool tags) and region (region tags) loops.
    """
    if len(tags) == 1:
        return [k]
    return [jax.random.fold_in(k, t) for t in tags]


def sample_clock_vector(procs: tuple, tags: tuple, k: jax.Array,
                        scale: jax.Array) -> jax.Array:
    """Stacked per-tag renewal samples × a traced scale vector.

    One implementation for the market's spot clocks and the region loop's
    job and spot clocks (same fold-in layout, same stacking order).
    """
    samples = [p.sample(kk) for p, kk in zip(procs, tagged_keys(tags, k))]
    return jnp.stack(samples) * scale


def sample_hazard_clocks(tags: tuple, k: jax.Array,
                         hazard: jax.Array) -> jax.Array:
    """``Exp(h_t)`` revocation clocks per tag; ``h_t = 0`` never fires (INF).

    Always tag-folded (the PR-2 preempt layout has no 1-pool shortcut).
    """
    u = jnp.stack([
        jax.random.exponential(jax.random.fold_in(k, t), dtype=jnp.float32)
        for t in tags
    ])
    return jnp.where(hazard > 0.0, u / jnp.maximum(hazard, jnp.float32(1e-30)),
                     _INF)


# ---------------------------------------------------------------------------
# Raw-bits → draws (slab mode)
# ---------------------------------------------------------------------------


def u01(bits: jax.Array) -> jax.Array:
    """uint32 bits → float32 uniforms on [0, 1) (24-bit resolution)."""
    # via int32: exact below 2**24, and Mosaic has no uint32 -> float cast
    return ((bits >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32)
            * np.float32(2.0 ** -24))


def exp_from_u(u: jax.Array) -> jax.Array:
    """Unit-rate exponential via inverse CDF (the sampler's ``-log1p(-U)``)."""
    return -jnp.log1p(-u)


def gumbel_from_u(u: jax.Array) -> jax.Array:
    """Standard Gumbel via inverse CDF, guarded at u = 0."""
    return -jnp.log(-jnp.log(jnp.maximum(u, np.float32(1e-12))))


def synth_key(bits: jax.Array) -> jax.Array:
    """Two uint32 slab columns → a raw threefry key for legacy kernel hooks.

    The fallback path for kernels without ``*_u`` hooks: the hook still
    receives a key and draws in-body (1-2 small threefry calls), but the
    engine's own per-event ladders and clock refreshes stay slab-driven.
    """
    return jnp.stack([bits[0], bits[1]])


def argmin_first(x: jax.Array) -> jax.Array:
    """``jnp.argmin`` of a 1-D array — the first index of its minimum —
    built from min-reductions, which Mosaic lowers for every dtype (its
    argmin takes float32 only).  Event bodies pick slots, pools and
    regions with it, so one traced body serves every executor."""
    iota = jax.lax.iota(jnp.int32, x.shape[0])
    return jnp.min(jnp.where(x == jnp.min(x), iota, np.int32(x.shape[0])))


def argmax_first(x: jax.Array) -> jax.Array:
    """``jnp.argmax`` twin of :func:`argmin_first`."""
    iota = jax.lax.iota(jnp.int32, x.shape[0])
    return jnp.min(jnp.where(x == jnp.max(x), iota, np.int32(x.shape[0])))


# ---------------------------------------------------------------------------
# Superposed Poisson preemption clock (shared law, host + traced)
# ---------------------------------------------------------------------------


def hazard_clock(hazard, u):
    """Time to the next preemption event under the superposed total hazard.

    ``min(Exp(h_1), …, Exp(h_P)) ~ Exp(Σ h_p)``: one inverse-CDF draw at the
    total hazard replaces the O(P) per-pool vector refresh; a zero total
    never fires (INF).  Host scalars take the pure-Python path (the cluster
    orchestrator's twin), traced inputs the jnp path the engine scans.
    """
    if not (isinstance(hazard, jax.Array) or isinstance(u, jax.Array)):
        total = float(np.sum(hazard))
        if total <= 0.0:
            return math.inf  # host clocks use true inf, traced ones _INF
        return -math.log1p(-float(u)) / total
    h = jnp.asarray(hazard, jnp.float32)
    total = jnp.sum(h)
    return jnp.where(total > 0.0,
                     exp_from_u(jnp.asarray(u, jnp.float32))
                     / jnp.maximum(total, jnp.float32(1e-30)),
                     _INF)


def thinning_pick(hazard, u):
    """Which pool fired: a categorical draw with weights ``h_p``.

    By memorylessness the argmin of independent exponential clocks is
    independent of their min, with P(pool p) = h_p / Σ h_q — so a fresh
    uniform thinned over the hazard cumsum reproduces the vector clocks'
    (firing time, firing pool) joint law exactly.  Zero-hazard pools are
    never picked.  Dual host/traced backend like :func:`hazard_clock`.
    """
    if not (isinstance(hazard, jax.Array) or isinstance(u, jax.Array)):
        cum = np.cumsum(np.asarray(hazard, np.float64))
        if cum[-1] <= 0.0:
            return 0
        return int(min(np.sum(float(u) * cum[-1] >= cum[:-1]),
                       len(cum) - 1))
    h = jnp.asarray(hazard, jnp.float32)
    # jnp.cumsum's left-to-right running sum, spelled out: Mosaic has no
    # cumsum
    cum = [h[0]]
    for p in range(1, h.shape[0]):
        cum.append(cum[-1] + h[p])
    x = jnp.asarray(u, jnp.float32) * cum[-1]
    pick = sum((x >= c).astype(jnp.int32) for c in cum[:-1])
    return jnp.minimum(pick, h.shape[0] - 1).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Slab layout: who owns which draw columns
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SlabLayout:
    """Static per-trace column map of one event's slab row.

    Spans are ``(start, n)`` uint32 column ranges; modes say how the
    corresponding kernel hook consumes its span: ``"u"`` = slab-aware hook
    (``admit_u`` / ``admit_market_u`` / ``on_preempt_u`` / ``route_u``)
    receiving float32 uniforms, ``"key"`` = two raw columns synthesized into
    a legacy key (:func:`synth_key`), ``"none"`` = hook absent.  The
    preempt span is always two columns: [superposed clock draw, thinning
    pick].  See docs/kernels.md ("Randomness protocol") for the authoring
    rules and EXPERIMENTS.md for the full table.
    """

    n_cols: int
    job: tuple[int, int]
    spot: tuple[int, int]
    admit: tuple[int, int]
    admit_mode: str  # "u" | "key"
    market_admit: bool  # admit span feeds admit_market (vs plain admit)
    preempt: tuple[int, int] | None
    on_preempt: tuple[int, int] | None
    on_preempt_mode: str  # "u" | "key" | "none"
    route: tuple[int, int] | None
    route_mode: str  # "u" | "key" | "none"

    def bits(self, x: jax.Array, span: tuple[int, int]) -> jax.Array:
        """Raw uint32 columns of one span (static slice)."""
        return x[span[0]:span[0] + span[1]]

    def uniforms(self, x: jax.Array, span: tuple[int, int]) -> jax.Array:
        """One span as float32 uniforms on [0, 1)."""
        return u01(self.bits(x, span))


def kernel_slab_cols(kernel, hook: str, n: int) -> int | None:
    """Columns a kernel's slab-aware ``hook`` owns, or None for fallback.

    A kernel is slab-aware for ``hook`` iff it defines BOTH ``{hook}_u``
    and ``slab_cols(hook, n)`` returning a non-None count (``n`` is the
    pool/region count, for choice rules whose width depends on it).
    """
    if getattr(kernel, hook + "_u", None) is None:
        return None
    slab_cols = getattr(kernel, "slab_cols", None)
    if slab_cols is None:
        return None
    return slab_cols(hook, n)


def choice_cols(choice: str, n: int) -> int:
    """Uniform columns a pool/region choice rule consumes (see
    ``choose_pool_u`` / ``choose_region_u``)."""
    if choice == "uniform":
        return 1
    if choice == "weighted":
        return n
    return 0  # deterministic argmin rules (and "home") draw nothing


def build_slab_layout(kernel, *, job_udim: int, spot_udim: int, n: int = 1,
                      preempt_on: bool = False, has_route: bool = False,
                      market: bool = False) -> SlabLayout:
    """Assign this trace's slab columns: engine clocks first, hooks after.

    Column order is [job refresh | spot refresh | admit hook | preempt
    clock+pick | on_preempt hook | route hook]; spans not needed by the
    static config are absent, so a degenerate config's layout reduces
    exactly to the simpler loop's (the slab analogue of the degenerate
    bitwise ledger).
    """
    cursor = 0

    def take(width: int) -> tuple[int, int]:
        nonlocal cursor
        span = (cursor, width)
        cursor += width
        return span

    job = take(job_udim)
    spot = take(spot_udim)
    # the market/region loops route admission to admit_market when the
    # kernel has one; the single-queue loop always uses plain admit
    market_admit = market and hasattr(kernel, "admit_market")
    hook = "admit_market" if market_admit else "admit"
    cols = kernel_slab_cols(kernel, hook, n)
    admit_mode = "key" if cols is None else "u"
    admit = take(KEY_SYNTH_COLS if cols is None else cols)
    preempt = take(2) if preempt_on else None
    on_preempt, on_preempt_mode = None, "none"
    if preempt_on and hasattr(kernel, "on_preempt"):
        cols = kernel_slab_cols(kernel, "on_preempt", n)
        on_preempt_mode = "key" if cols is None else "u"
        on_preempt = take(KEY_SYNTH_COLS if cols is None else cols)
    route, route_mode = None, "none"
    if has_route:
        cols = kernel_slab_cols(kernel, "route", n)
        route_mode = "key" if cols is None else "u"
        route = take(KEY_SYNTH_COLS if cols is None else cols)
    return SlabLayout(
        n_cols=max(cursor, 1), job=job, spot=spot, admit=admit,
        admit_mode=admit_mode, market_admit=market_admit, preempt=preempt,
        on_preempt=on_preempt, on_preempt_mode=on_preempt_mode, route=route,
        route_mode=route_mode)


def process_udim(proc) -> int:
    """Uniform columns an arrival process needs per draw, with a clear
    error pointing at ``rng="split"`` for families without a slab sampler."""
    dim = getattr(proc, "u_dim", None)
    if dim is None:
        raise NotImplementedError(
            f"{proc!r} has no slab sampler (u_dim/sample_u); "
            "run this configuration with rng='split'")
    return int(dim)


# ---------------------------------------------------------------------------
# Slab generation (one counter-based bits call per float32 window)
# ---------------------------------------------------------------------------


def window_slab(key: jax.Array, n_events: int,
                n_cols: int) -> tuple[jax.Array, jax.Array]:
    """Advance the lane key one window; return (new_key, (n_events, n_cols)
    uint32 slab).  Typed and raw uint32 keys produce the same stream, so
    the XLA scan path (typed lane keys) and the Pallas lane layout (raw
    keys) consume bitwise-identical slabs.
    """
    ks = jax.random.split(key)
    return ks[0], jax.random.bits(ks[1], (n_events, n_cols), jnp.uint32)


def lane_slab_keys(key: jax.Array, n_windows: int) -> jax.Array:
    """One lane's per-window slab keys, (n_windows, 2) raw uint32 words.

    Walks the :func:`window_slab` ladder: window ``w``'s slab is
    ``jax.random.bits(keys[w], (n_ev_w, n_cols), uint32)``, bitwise the
    slab the scan executor draws for that window.  The batched-event
    executors carry these keys instead of the slabs themselves and build
    each event's row with :func:`slab_row`.
    """
    keys = []
    for _ in range(n_windows):
        ks = jax.random.split(key)
        key = ks[0]
        keys.append(ks[1])
    return jnp.stack(keys)
