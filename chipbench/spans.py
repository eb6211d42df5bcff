"""The program's own phase spans, for the per-layer metrics that read them.

Every entry point of the program (``run_sweep``, ``run_market_sweep``,
``what_if_sweep``, ...) spans its whole call and splits it into sibling
phase spans, on the profiler's clock and the calling thread:
``repro.prep``, ``repro.dispatch``, ``repro.wait``, ``repro.fetch`` and
``repro.summarize``.  Each executable it builds (an XLA compile or a load
from the persistent cache) leaves a zero-length ``repro.compiled`` span.
``trace.load`` keeps them: they run on the thread of the ``bench.call``
spans.  A trace with no phase span comes from a program without them, and
every reader here then finds nothing (``None``).  The names are written
out here, not imported from the program, so the readers load over any
version of it.
"""
from __future__ import annotations

from chipbench.trace import clip, merge, total

PREP, DISPATCH, WAIT, FETCH, SUMMARIZE = (
    "repro.prep", "repro.dispatch", "repro.wait", "repro.fetch",
    "repro.summarize")
PHASES = (PREP, DISPATCH, WAIT, FETCH, SUMMARIZE)
#: The phases in which the host works (the wait is the device's time).
HOST_PHASES = (PREP, DISPATCH, FETCH, SUMMARIZE)
COMPILED = "repro.compiled"


def _phased(run):
    """The run's trace, if it holds the program's phase spans."""
    tr = run.trace
    if tr is None or not any(n in PHASES for _, _, n in tr.spans):
        return None
    return tr


def phase_ms(run, *names: str):
    """Summed duration of the spans of ``names``, clipped to the
    ``bench.call`` spans, per call, in ms."""
    tr = _phased(run)
    if tr is None:
        return None
    calls = tr.calls()
    if not calls:
        return None
    spans = merge((s, e) for s, e, n in tr.spans if n in names)
    return 1e3 * sum(total(clip(spans, s, e)) for s, e in calls) / len(calls)


def compiles(run):
    """``repro.compiled`` markers in the window of the calls."""
    tr = _phased(run)
    if tr is None:
        return None
    if not tr.calls():
        return None
    lo, hi = tr.window()
    return sum(1 for s, _, n in tr.spans if n == COMPILED and lo <= s <= hi)
