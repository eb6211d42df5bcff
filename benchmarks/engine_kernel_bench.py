"""Pallas batched-event kernel vs the XLA scan executor, at equal events.

Times the same (r × seeds) grid through both executors of the sweep engine
(``impl="xla"`` vs ``impl="pallas"``), single-pool and 4-pool market, and
records the equivalence ledger while timing: bitwise vs the scan-reference
oracle (``impl="ref"``), integer-exact + max float rtol vs the production
XLA executor.  Writes BENCH_engine_kernel.json next to the repo root
(smoke runs write a separate gitignored BENCH_engine_kernel_smoke.json).

Interpretation of the numbers (recorded in the JSON):

  * on a compiled backend (TPU: ``interpret=False``) the kernel keeps the
    (tile, rmax) engine state resident in VMEM across a whole event window,
    so its events/s is the headline claim (target ≥2× the XLA executor
    events/s of BENCH_sweep.json / BENCH_market.json at equal total
    events);
  * with ``set_interpret(True)`` (``benchmarks/run.py --interpret``, the
    CPU-host mode) the kernel runs through the Pallas *interpreter* —
    those numbers measure dispatch overhead + bitwise parity, NOT kernel
    speed, and are reported under ``"interpret": true`` so they are never
    compared against the compiled target.  Timing the compiled kernel
    (the default) without a TPU raises.

The compiled kernel runs the slab stream only (``rng="slab"``); the
interpreted mode keeps the frozen split stream its CPU baselines use.

Compile time is recorded separately from the steady-state numbers
(``benchmarks/_timing.py``).
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.timing import provenance, time_compiled
from benchmarks.market_bench import bench_market
from repro.core import (
    Exponential,
    NoticeAwareKernel,
    ThreePhaseKernel,
    run_market_sweep,
    run_sweep,
)
from repro.core.engine import INT_STATS as _INT_STATS

LAM, MU, K = 1 / 12, 1 / 24, 10.0
_REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")

_SCALE = 1.0
_INTERPRET = False

#: kernel-launch geometry recorded in the JSON (see EXPERIMENTS.md)
TILE = 256


def set_scale(scale: float) -> None:
    global _SCALE
    _SCALE = scale


def set_interpret(interpret: bool) -> None:
    """Time the Pallas interpreter (True) or the compiled kernel (False)."""
    global _INTERPRET
    _INTERPRET = interpret


def _bench_json_path() -> str:
    name = ("BENCH_engine_kernel.json" if _SCALE == 1.0
            else "BENCH_engine_kernel_smoke.json")
    return os.path.join(_REPO_ROOT, name)


def _stats_equal(a: dict, b: dict) -> bool:
    return all(np.array_equal(np.asarray(v), np.asarray(b[n]))
               for n, v in a.items())


def _parity(pal: dict, ref: dict, xla: dict) -> dict:
    """The two-sided equivalence record: bitwise vs the scan reference
    (impl="ref", the oracle), int-exact + max float rtol vs the production
    XLA executor (see EXPERIMENTS.md for why these differ)."""
    int_eq = all(np.array_equal(np.asarray(xla[n]), np.asarray(pal[n]))
                 for n in _INT_STATS if n in xla)
    rel = 0.0
    for n, v in xla.items():
        if n in _INT_STATS:
            continue
        a, b = np.asarray(v, np.float64), np.asarray(pal[n], np.float64)
        denom = np.maximum(np.abs(a), 1e-30)
        rel = max(rel, float(np.max(np.abs(a - b) / denom)))
    return {"bit_equal_ref": _stats_equal(ref, pal),
            "int_equal_xla": int_eq,
            "max_float_rtol_xla": rel}


def _baseline(name: str, key: str) -> float | None:
    path = os.path.join(_REPO_ROOT, name)
    if not os.path.exists(path):
        return None
    return json.load(open(path)).get(key)


def measure_engine_kernel(n_r: int = 16, n_seeds: int = 4,
                          n_events: int | None = None,
                          rmax: int = 64) -> dict:
    if n_events is None:
        n_events = max(2_000, int(50_000 * _SCALE))
    interpret = _INTERPRET
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"engine_kernel bench: the compiled kernel needs a TPU, the "
            f"backend is {jax.default_backend()!r}; set_interpret(True) "
            f"(run.py --interpret) times the interpreter instead")
    rng = "split" if interpret else "slab"
    job, spot = Exponential(LAM), Exponential(MU)
    rs = jnp.linspace(0.25, 4.0, n_r)
    key = jax.random.key(0)
    common = dict(k=K, n_events=n_events, key=key, n_seeds=n_seeds,
                  rmax=rmax, rng=rng)
    grid_points = n_r * n_seeds
    total_events = grid_points * n_events

    compile_s = {}

    def timed(fn, label=None):
        out, timing = time_compiled(fn)
        if label:
            compile_s[label] = timing["t_compile_s"]
        return out, timing["t_run_s"]

    result = {
        "grid_points": grid_points,
        "n_r": n_r,
        "n_seeds": n_seeds,
        "n_events_per_point": n_events,
        "total_events": total_events,
        "rmax": rmax,
        "rng": rng,
        "tile": TILE,
        "event_block": min(1 << 16, n_events),
        "interpret": interpret,
        "backend": jax.default_backend(),
        "baseline_sweep_events_per_s": _baseline(
            "BENCH_sweep.json", "sweep_events_per_s"),
        "baseline_market_events_per_s": _baseline(
            "BENCH_market.json", "market_events_per_s"),
        "provenance": provenance(seed=0, telemetry="off"),
    }

    kern = ThreePhaseKernel()
    xla, t_xla = timed(lambda: run_sweep(job, spot, kern, {"r": rs},
                                         **common), "single_xla")
    pal, t_pal = timed(lambda: run_sweep(job, spot, kern, {"r": rs},
                                         impl="pallas", tile=TILE,
                                         interpret=interpret, **common),
                       "single_pallas")
    ref = run_sweep(job, spot, kern, {"r": rs}, impl="ref", **common)
    result["single"] = {
        "t_xla_s": t_xla,
        "t_pallas_s": t_pal,
        "xla_events_per_s": total_events / t_xla,
        "pallas_events_per_s": total_events / t_pal,
        "pallas_speedup_x": t_xla / t_pal,
        **_parity(pal, ref, xla),
    }

    market = bench_market()  # the reference 4-pool market
    mkern = NoticeAwareKernel(checkpoint_time=0.05)
    xla_m, t_xla_m = timed(lambda: run_market_sweep(
        job, market, mkern, {"r": rs}, **common), "market_xla")
    pal_m, t_pal_m = timed(lambda: run_market_sweep(
        job, market, mkern, {"r": rs}, impl="pallas", tile=TILE,
        interpret=interpret, **common), "market_pallas")
    ref_m = run_market_sweep(job, market, mkern, {"r": rs}, impl="ref",
                             **common)
    result["market"] = {
        "n_pools": market.n_pools,
        "t_xla_s": t_xla_m,
        "t_pallas_s": t_pal_m,
        "xla_events_per_s": total_events / t_xla_m,
        "pallas_events_per_s": total_events / t_pal_m,
        "pallas_speedup_x": t_xla_m / t_pal_m,
        **_parity(pal_m, ref_m, xla_m),
    }

    result["t_compile_s"] = compile_s
    with open(_bench_json_path(), "w") as f:
        json.dump(result, f, indent=2)
    return result


def bench_engine_kernel():
    """Benchmark-harness entry: rows + headline (pallas events/s, single)."""
    res = measure_engine_kernel()
    mode = "interpret" if res["interpret"] else "compiled"
    rows = []
    for name in ("single", "market"):
        r = res[name]
        rows.append({
            "name": f"engine_kernel/{name}/{res['grid_points']}pt_{mode}",
            "us_per_call": r["t_pallas_s"] * 1e6,
            "derived": (
                f"{res['grid_points']} pts × {res['n_events_per_point']} ev "
                f"({mode}; tile={res['tile']}): "
                f"pallas={r['pallas_events_per_s']/1e6:.2f}M ev/s "
                f"xla={r['xla_events_per_s']/1e6:.2f}M ev/s "
                f"({r['pallas_speedup_x']:.2f}x; "
                f"bit_equal_ref={r['bit_equal_ref']} "
                f"int_equal_xla={r['int_equal_xla']})"
            ),
        })
    return rows, res["single"]["pallas_events_per_s"]
