"""Shared reader of the JAX profiler's trace, for the per-layer metrics.

A traced run profiles a few whole calls.  ``load`` reduces the profile
(``*.xplane.pb``, read with ``jax.profiler.ProfileData``) to what the
metric readers need, all on the profiler's one clock, in seconds:

* ``ops``: per device plane (``/device:TPU:<n>``), the XLA ops that ran,
  as ``[start, end, name, category]``.  On a TPU the profiler names an op
  by its whole HLO instruction (``%name = (shapes) opcode(operands), ...``);
  ``name`` keeps the part before `` = ``, and ``category`` is
  :data:`KERNEL_CATEGORY` for a custom call (the Mosaic kernel: the only
  custom calls in these programs), else the profiler's ``hlo_category``;
* ``spans``: the benchmark's own host spans (``bench.*``: prep, warm-up,
  each call, the check) and every host event on the threads that ran
  them, as ``[start, end, name]``; idle device time is put down to the
  innermost of them.

The reduction keeps to the window from the first call's start to the
last call's end, so the profiler's own start and stop fall outside it.
"""
from __future__ import annotations

import heapq
import json
import pathlib
from dataclasses import dataclass

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
CALL_SPAN = "bench.call"
#: The HLO category of a Mosaic (Pallas) kernel's op on the device.
KERNEL_CATEGORY = "custom-call"


def merge(intervals) -> list:
    """The union of ``(start, end)`` intervals, sorted and disjoint."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


@dataclass
class Trace:
    ops: dict   # device -> [[start, end, name, category], ...]
    spans: list  # [[start, end, name], ...] host events of the bench threads

    def __post_init__(self):
        self._busy = {}  # device -> busy(device), the union of all its ops

    # ------------------------------------------------------------ window
    def calls(self) -> list:
        return sorted([s, e] for s, e, n in self.spans if n == CALL_SPAN)

    def window(self) -> tuple:
        calls = self.calls()
        if not calls:
            raise ValueError("no bench.call span in the trace")
        return calls[0][0], max(e for _, e in calls)

    def window_s(self) -> float:
        lo, hi = self.window()
        return hi - lo

    # -------------------------------------------------------------- busy
    def busy(self, device, where=None) -> list:
        """Union of the device's op intervals in the window; ``where``
        keeps only the ops it accepts."""
        if where is None and device in self._busy:
            return self._busy[device]
        lo, hi = self.window()
        out = clip(merge((s, e) for s, e, n, c in self.ops[device]
                         if where is None or where(n, c)), lo, hi)
        if where is None:
            self._busy[device] = out
        return out

    def busy_s(self, where=None) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(total(self.busy(d, where)) for d in self.ops) / len(self.ops)

    def idle_pct(self) -> float:
        """Share of the window in which no op ran, mean over devices, %."""
        return 100.0 * (1.0 - self.busy_s() / self.window_s())

    def busy_within(self, spans) -> float:
        """Busy seconds inside ``spans``, averaged over the devices."""
        if not self.ops:
            return 0.0
        per = []
        for d in self.ops:
            busy = self.busy(d)
            per.append(sum(total(clip(busy, s, e)) for s, e in spans))
        return sum(per) / len(per)

    # --------------------------------------------------------- breakdown
    def host_at(self, t: float) -> str:
        """The innermost host event running at ``t``."""
        best = None
        for s, e, n in self.spans:
            if s <= t <= e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return best[2] if best else "(no host event)"

    def breakdown(self, top: int = 10) -> dict:
        """The device ops that took most time (seconds, summed over the
        devices) and the longest idle gaps by what the host was doing."""
        lo, hi = self.window()
        by_name = {}
        for evs in self.ops.values():
            for s, e, n, _ in evs:
                by_name[n] = by_name.get(n, 0.0) + (min(e, hi) - max(s, lo)
                                                   if e > lo and s < hi
                                                   else 0.0)
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for d in self.ops:
            edges = [lo] + [x for iv in self.busy(d) for x in iv] + [hi]
            gaps.extend((e - s, s, e) for s, e in zip(edges[0::2], edges[1::2])
                        if e > s)
        longest = heapq.nlargest(top, gaps)
        return {"device_ops": [[n, t] for n, t in ops if t > 0],
                "idle_gaps": [[self.host_at((s + e) / 2), t]
                              for t, s, e in longest]}

    def to_json(self) -> str:
        return json.dumps({"ops": self.ops, "spans": self.spans})


def kernel_ns_per_event(run):
    """Device time of the batched-event kernel per simulated lane-event:
    the kernel's op time, summed over the devices, over the lane-events
    of the traced calls (``n_events + burn_in`` per lane and call).  The
    kernel is the op of the Mosaic custom-call category."""
    tr = run.trace
    if tr is None or not tr.ops or not run.calls:
        return None
    kernel_s = tr.busy_s(lambda n, c: c == KERNEL_CATEGORY) * len(tr.ops)
    if kernel_s <= 0:
        return None
    return kernel_s * 1e9 / run.lane_events


def _op(event) -> list:
    """``[start, end, name, category]`` of a device op, in seconds."""
    name, _, hlo = event.name.partition(" = ")
    if f" {KERNEL_CATEGORY}(" in hlo:
        category = KERNEL_CATEGORY
    else:
        category = next((str(v) for k, v in event.stats
                         if k == "hlo_category"), "")
    return [event.start_ns * 1e-9, event.end_ns * 1e-9, name, category]


def load(logdir: pathlib.Path) -> Trace:
    """Reduce the newest profile under ``logdir``."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(logdir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no profile under {logdir}")
    data = ProfileData.from_file(str(files[-1]))
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(plane.name, []).extend(
                        _op(e) for e in line.events)
        else:
            for line in plane.lines:
                events = list(line.events)
                if any(e.name.startswith(SPAN_PREFIX) for e in events):
                    spans.extend([e.start_ns * 1e-9, e.end_ns * 1e-9, e.name]
                                 for e in events)
    return Trace(ops, spans)
