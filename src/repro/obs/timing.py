"""Timing, provenance, and profiler scopes — the host half of ``repro.obs``.

The small tools every measurement surface in the repo shares:

* :func:`time_compiled` — the bench harness's compile-vs-steady-state
  split (absorbed from ``benchmarks/_timing.py``, which now re-exports
  it).  The first call pays trace + XLA compile + one run; steady state
  is the mean of further calls blocked to completion.
* :func:`provenance` — the audit stamp every ``BENCH_*.json`` carries:
  git commit, jax version, backend, device kind and count, platform,
  python.  A BENCH number
  without its commit and backend is unfalsifiable; with them the BENCH
  trajectory across PRs is a real measurement series.
* :func:`annotate` — a named ``jax.profiler`` trace scope, on the
  profiler's clock and the calling thread; zero cost when no profiler is
  attached.
* :class:`EntrySpan` — the one span of an entry-point call (the engine's
  ``run_*`` loops, the adaptive learners, the orchestrator's what-if
  sweeps), from entry until the answer is on the host, split into the
  sibling phases of :data:`PHASES` (``repro.prep``, ``repro.dispatch``,
  ``repro.wait``, ``repro.fetch``, ``repro.summarize``).
* :func:`compile_count` — executables built in this process (XLA compile
  or persistent-cache load), each also marked by a zero-length
  ``repro.compiled`` span inside the phase that built it.

:func:`enable_compile_cache` turns on JAX's persistent compilation cache
for a script (``chip_smoke.py``, ``benchmarks/run.py``); importing the
library never does.
"""
from __future__ import annotations

import os
import pathlib
import platform as _platform
import subprocess
import sys
import threading
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
    """A named profiler trace scope (``with annotate("run_sweep"): ...``):
    ``jax.profiler.TraceAnnotation``, on the profiler's clock and the
    calling thread.  Zero overhead when no profiler session is active."""
    return jax.profiler.TraceAnnotation(name)


#: The phases of an entry-point call, in order; each is a ``repro.<phase>``
#: span, a sibling of the others inside the call's span:
#: ``prep`` (argument checks, array conversion and broadcast, key split),
#: ``dispatch`` (the jitted executor call, until it returns),
#: ``wait`` (the host blocked on the device), ``fetch`` (one
#: ``jax.device_get`` of the whole stats pytree) and ``summarize`` (the
#: float64 host reduction into the returned dict).
PHASES = ("prep", "dispatch", "wait", "fetch", "summarize")
#: JAX's monitoring event around ``compile_or_get_cached``: it fires on
#: every miss of the in-memory executable cache, compiled or loaded.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
#: The zero-length span marking one such event.
COMPILED_SPAN = "repro.compiled"

_compiles = 0
_listening = False
_lock = threading.Lock()
_local = threading.local()


def compile_count() -> int:
    """Executables built in this process since the first entry-point call
    (a changed grid shape is one more): the count of :data:`COMPILE_EVENT`."""
    return _compiles


def _on_event_duration(event: str, duration_secs: float, **kwargs) -> None:
    global _compiles
    if event != COMPILE_EVENT:
        return
    with _lock:
        _compiles += 1
    with annotate(COMPILED_SPAN):
        pass


def _listen() -> None:
    """Register the compile listener, once (never at import)."""
    global _listening
    with _lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration)
            _listening = True


class EntrySpan:
    """The span of one entry-point call and its phases.

    ``with EntrySpan("repro.run_sweep[xla]") as call:`` opens the call's
    span and its ``repro.prep`` phase; ``call.phase(name)`` closes the
    open phase and opens the next; ``call.to_host(stats)`` blocks on
    ``stats`` (``repro.wait``), copies the whole pytree to the host in one
    ``jax.device_get`` (``repro.fetch``) and opens ``repro.summarize``;
    leaving the block closes the phase and the call's span, so the span
    ends when the returned dict is on the host.

    An entry point called from inside another on the same thread (the
    orchestrator's ``what_if_sweep`` runs ``run_market_sweep``) joins the
    outer call: it gets the outer object, asking for the phase that is
    open continues it, and the outer span is the call's one span.
    """

    def __init__(self, name: str):
        self.name = name
        self._joined = False
        self._span = self._phase_span = self._phase = None

    def __enter__(self) -> "EntrySpan":
        _listen()
        outer = getattr(_local, "call", None)
        if outer is not None:
            self._joined = True
            return outer
        _local.call = self
        self._span = annotate(self.name)
        self._span.__enter__()
        self.phase("prep")
        return self

    def phase(self, name: str) -> None:
        """Close the open phase and open ``repro.<name>`` (one of
        :data:`PHASES`); asking for the open phase continues it."""
        if name not in PHASES:
            raise ValueError(f"unknown phase {name!r} (expected one of "
                             f"{PHASES})")
        if name == self._phase:
            return
        if self._phase_span is not None:
            self._phase_span.__exit__(None, None, None)
        self._phase = name
        self._phase_span = annotate(f"repro.{name}")
        self._phase_span.__enter__()

    def to_host(self, stats):
        """``stats`` on the host as numpy, through the wait and fetch
        phases; the summarize phase is open on return."""
        self.phase("wait")
        jax.block_until_ready(stats)
        self.phase("fetch")
        stats = jax.device_get(stats)
        self.phase("summarize")
        return stats

    def __exit__(self, *exc) -> bool:
        if self._joined:
            return False
        self._phase_span.__exit__(*exc)
        self._span.__exit__(*exc)
        _local.call = None
        return False
