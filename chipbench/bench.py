"""One run of one cell: set-up, measured window, check, result line.

Everything a cell needs is found by name from ``BENCHMARK.json``:

* the configuration ``configs/<config>.json`` (sizes, guarantees, limits),
  its program adapter ``configs/<config>.py`` (class ``Program``) and its
  plain reference ``configs/<config>_reference.py`` (``compare``);
* the traffic mix ``traffic/<traffic>.json``, read by ``generator.Load``;
* each metric ``metrics/<metric>.py`` (``read(run)``), for the metrics
  whose ``workloads`` list the cell (or that have no such list).

A later cell, configuration, mix or metric is new files and new entries.
"""
from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import shutil
import statistics
import sys
import time
import traceback
from dataclasses import dataclass, field

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
#: Made at run time inside the checkout (gitignored): JAX's persistent
#: compilation cache, at a fixed path so that every run of a cell after
#: the first finds its programs, and the traced run's profile.
CACHE = ".chipbench_cache"


class Refused(RuntimeError):
    """The run cannot be made here: no result is printed."""


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise Refused(f"no {path.name} in {root}")
    return json.loads(path.read_text())


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise Refused(f"no {what} named {name!r} in BENCHMARK.json")


def load_json(kind: str, name: str, here: pathlib.Path = HERE) -> dict:
    path = here / kind / f"{name}.json"
    if not path.is_file():
        raise Refused(f"missing {path.relative_to(here.parent)}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path):
    """Import a file by path; metric names hold dots, so not by import."""
    if not path.is_file():
        raise Refused(f"missing {path.name}")
    name = f"chipbench_{path.parent.name}_{path.stem}".replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_metrics(bench: dict, cell: str, section: str) -> list[dict]:
    """The metrics of ``section`` that ``cell`` reports."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]


@dataclass
class Cell:
    """A workload entry with its configuration, mix and modules loaded."""

    workload: dict
    config: dict
    traffic: dict
    adapter: object
    reference: object

    @classmethod
    def load(cls, bench: dict, name: str, here: pathlib.Path = HERE):
        w = find(bench["workloads"], name, "workload")
        cfg_name = w["config"]
        find(bench["configs"], cfg_name, "config")
        return cls(w, load_json("configs", cfg_name, here),
                   load_json("traffic", w["traffic"], here),
                   load_module(here / "configs" / f"{cfg_name}.py"),
                   load_module(here / "configs" / f"{cfg_name}_reference.py"))


@dataclass
class Run:
    """What one run measured; the metric readers read it."""

    setup_s: float
    lane_events_per_call: int
    calls: list = field(default_factory=list)  # (start, end) host seconds
    answers: list = field(default_factory=list)
    trace: object = None  # chipbench.trace.Trace of the traced calls

    @property
    def window_s(self) -> float:
        return self.calls[-1][1] - self.calls[0][0] if self.calls else 0.0

    @property
    def lane_events(self) -> int:
        return self.lane_events_per_call * len(self.calls)


def span(name: str):
    """A host span of the benchmark's own, on the profiler's clock."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def tpu_devices(chips: int) -> list:
    """The chips this cell runs on; refuses a host without them."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:  # no backend at all
        raise Refused(f"JAX found no device: {e}") from e
    if devices[0].platform != "tpu":
        raise Refused(f"JAX found no TPU (platform "
                      f"{devices[0].platform!r}); nothing was run")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX sees "
                      f"{len(devices)}")
    return devices


def enable_compile_cache(root: pathlib.Path = ROOT) -> None:
    """JAX's persistent cache inside the checkout, for every program."""
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      str(root / CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_window(load, run: Run, seconds: float,
               max_calls: int | None = None) -> None:
    """Whole calls back to back until the first that ends after
    ``seconds`` (or the ``max_calls``-th); an exception propagates."""
    start = time.perf_counter()
    while True:
        t0 = time.perf_counter()
        with span("bench.call"):
            answer = load.call(len(run.calls) + 1)
        t1 = time.perf_counter()
        run.calls.append((t0, t1))
        run.answers.append(answer)
        if t1 - start >= seconds or len(run.calls) == max_calls:
            return


def traced_window(load, run: Run, seconds: float, logdir: pathlib.Path,
                  max_calls: int) -> None:
    """The window under the profiler, at most ``max_calls`` calls."""
    import jax
    from chipbench import trace

    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(logdir), profiler_options=opts)
    try:
        run_window(load, run, seconds, max_calls)
    finally:
        jax.profiler.stop_trace()
    run.trace = trace.load(logdir)
    shutil.rmtree(logdir, ignore_errors=True)


def prepare(cell: Cell, control: bool = False):
    """The configuration's program; ``control`` puts the control in the
    program's place: the program on the configuration with a stated
    guarantee broken."""
    cfg = cell.adapter.control(cell.config) if control else cell.config
    return cell.adapter.Program(cfg)


def cache_entries(root: pathlib.Path = ROOT) -> int:
    """Files in the persistent compilation cache: a set-up that adds some
    compiled them, one that adds none found every program there."""
    path = root / CACHE / "jax"
    return sum(1 for p in path.rglob("*") if p.is_file()) if path.is_dir() else 0


def memory_peak_bytes(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def checks(cell: Cell, rs, answers: list) -> dict:
    """Each compared number beside its limit, in the configuration's order."""
    got = cell.reference.compare(cell.config, cell.traffic, rs, answers)
    return {name: {"value": got[name], "limit": limit}
            for name, limit in cell.config["limits"].items()}


def passed(result: dict) -> bool:
    return all(isinstance(c["value"], (int, float))
               and math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in result.values())


class MissingMetric(RuntimeError):
    """A metric that the cell reports found nothing to read."""


def read_metrics(bench: dict, workload: str, section: str, run: Run,
                 here: pathlib.Path = HERE) -> dict:
    """Each metric's reader.  Every metric read here is one the cell has
    to report, so a reader that finds nothing (a kernel it cannot find in
    the trace, say) fails the run rather than leave the metric out."""
    out = {}
    for m in cell_metrics(bench, workload, section):
        value = load_module(here / "metrics" / f"{m['name']}.py").read(run)
        if value is None:
            raise MissingMetric(f"{m['name']} found nothing to read in "
                                f"this run of {workload}")
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def latency_line(run: Run) -> str:
    lat = sorted((b - a) * 1e3 for a, b in run.calls)
    if not lat:
        return "calls: none"
    p95 = lat[min(len(lat) - 1, math.ceil(0.95 * len(lat)) - 1)]
    return (f"calls: {len(lat)}, latency ms p50 {statistics.median(lat)!r} "
            f"p95 {p95!r} max {lat[-1]!r}")


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_start: float, root: pathlib.Path = ROOT,
             devices=None, control: bool = False,
             keep_trace: pathlib.Path | None = None,
             calls: int | None = None) -> dict:
    """Set up, measure and check one cell; return the result line.

    ``devices`` skips the look for a chip (tests pass the CPU's);
    ``control`` runs the configuration's control in the program's place
    (tests and ``readings.py`` only); ``keep_trace`` writes the reduced
    trace of a traced run there (``tools/record_trace.py``); ``calls``
    ends the untraced window at that many calls, if ``seconds`` have not
    ended it first (tests only)."""
    bench = load_benchmark(root)
    cell = Cell.load(bench, workload, root / "chipbench")
    chips = int(cell.workload["chips"])
    if devices is None:
        devices = tpu_devices(chips)
    enable_compile_cache(root)
    cached = cache_entries(root)
    from chipbench.generator import Load

    with span("bench.prep"):
        load = Load(prepare(cell, control), cell.traffic, seed)
    with span("bench.warmup"):
        load.call(0)  # the window's exact shapes: compiled or cache-loaded
    run = Run(setup_s=time.perf_counter() - t_start,
              lane_events_per_call=load.lane_events)
    compiled = cache_entries(root) - cached

    error = None
    try:
        if trace:
            traced_window(load, run, seconds,
                          root / CACHE / "trace" / workload,
                          int(cell.traffic["traced_calls"]))
        else:
            run_window(load, run, seconds, calls)
    except Exception:  # a call that fails is an answer that never came
        error = traceback.format_exc()
    used = devices[:chips]
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": memory_peak_bytes(used)}
    section = "per_layer" if trace else "end_to_end"
    metrics = read_metrics(bench, workload, section, run,
                           root / "chipbench") if error is None else {}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s()

    compared = checks(cell, load.rs, run.answers)
    failed = int(compared.get("malformed", {}).get("value", 0))
    if error is not None:
        failed += 1
        print(error, file=sys.stderr)
    result = {"correct": error is None and bool(run.answers)
              and passed(compared),
              "attempted": len(run.answers) + (error is not None),
              "failed": failed, "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        result["breakdown"] = run.trace.breakdown()
        if keep_trace is not None:
            keep_trace.write_text(run.trace.to_json())
    # A set-up that wrote programs to the cache compiled them: the first
    # run of a cell in a checkout, whose setup_s is not a cached set-up.
    result["setup_compiled"] = compiled
    print(f"set-up: {run.setup_s!r} s, {compiled} programs compiled "
          f"into the cache; in the window: "
          f"{cache_entries(root) - cached - compiled}", file=sys.stderr)
    print(latency_line(run), file=sys.stderr)
    for name, c in compared.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    result["checks"] = compared
    return result

