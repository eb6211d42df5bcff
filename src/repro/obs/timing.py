"""Timing, provenance, and profiler scopes — the host half of ``repro.obs``.

Three small tools every measurement surface in the repo shares:

* :func:`time_compiled` — the bench harness's compile-vs-steady-state
  split (absorbed from ``benchmarks/_timing.py``, which now re-exports
  it).  The first call pays trace + XLA compile + one run; steady state
  is the mean of further calls blocked to completion.
* :func:`provenance` — the audit stamp every ``BENCH_*.json`` carries:
  git commit, jax version, backend, device kind and count, platform,
  python.  A BENCH number
  without its commit and backend is unfalsifiable; with them the BENCH
  trajectory across PRs is a real measurement series.
* :func:`annotate` — named ``jax.profiler`` trace scopes on the engine
  entry points, the adaptive learner, and the orchestrator's what-if
  sweeps, so an ``xprof``/``perfetto`` capture of a sweep attributes
  device time to the loop that spent it.  Compiles to nothing when no
  profiler is attached; falls back to a null context where the profiler
  API is unavailable (minimal CPU wheels).

:func:`enable_compile_cache` turns on JAX's persistent compilation cache
for a script (``chip_smoke.py``, ``benchmarks/run.py``); importing the
library never does.
"""
from __future__ import annotations

import contextlib
import os
import pathlib
import platform as _platform
import subprocess
import sys
import time

import jax


def time_compiled(call, *, runs: int = 1):
    """Time ``call`` (a 0-arg closure returning a pytree) compile + steady.

    Returns ``(result, timing)`` with ``timing = {"t_first_s", "t_run_s",
    "t_compile_s"}``: the first call pays trace + compile + one run; the
    steady-state number is the mean of ``runs`` further calls, each blocked
    to completion.  ``t_compile_s`` is the difference, floored at zero.
    """
    t0 = time.perf_counter()
    out = jax.block_until_ready(call())
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for _ in range(runs):
        out = jax.block_until_ready(call())
    t_run = (time.perf_counter() - t0) / runs
    return out, {"t_first_s": t_first, "t_run_s": t_run,
                 "t_compile_s": max(t_first - t_run, 0.0)}


def _git_commit() -> str:
    try:
        return subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            timeout=5, check=True).stdout.strip()
    except Exception:  # no git / not a checkout — the stamp still works
        return "unknown"


def provenance(**extra) -> dict:
    """The measurement-audit stamp for BENCH jsons (and anything else).

    Keyword args are merged in verbatim — benches pass ``seed=`` and
    ``telemetry=`` so a BENCH file records the exact configuration that
    produced its numbers.
    """
    stamp = {
        "git_commit": _git_commit(),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "platform": _platform.platform(),
        "python": sys.version.split()[0],
    }
    stamp.update(extra)
    return stamp


#: Where the persistent compilation cache lives when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: one fixed, gitignored directory
#: of the checkout (the path is part of the cache key, so it never moves).
COMPILE_CACHE_DIR = (pathlib.Path(__file__).resolve().parents[3]
                     / ".jax_compile_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; return its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` is JAX's own setting and is left
    alone; otherwise the cache goes to :data:`COMPILE_CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def annotate(name: str):
    """A named profiler trace scope (``with annotate("run_sweep"): ...``).

    Uses ``jax.profiler.TraceAnnotation`` when available so the scope
    shows up on the device timeline of a profiler capture; otherwise a
    null context.  Zero overhead when no profiler session is active.
    """
    trace_annotation = getattr(jax.profiler, "TraceAnnotation", None)
    if trace_annotation is None:
        return contextlib.nullcontext()
    return trace_annotation(name)
