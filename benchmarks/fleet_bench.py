"""Fleet scaling: the ``shard="lanes"`` sweep across devices.

On a TPU backend the sweep runs in this process over the real local
devices (``lane_mesh(n)`` for n = 1, 2, 4, ... up to the device count): a
process that holds the chip never starts a child that would need it.  On
the CPU backend each device count runs in its own subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — the flag must be
set before the JAX backend initializes, so the parent (which holds the
single host device) cannot simulate more devices itself.  Either way one
sharded single-queue sweep (``impl="xla"``, ``rng="slab"``, the
recommended fast path) is timed with
:func:`repro.obs.timing.time_compiled`, so the curve carries the
compile-vs-steady split per device count.

Writes BENCH_fleet.json (BENCH_fleet_smoke.json under ``--smoke``) with a
``devices → {t_run_s, t_compile_s, events_per_s}`` scaling curve and the
usual provenance stamp.  The headline (guarded by CI's suite manifest) is
the 1-device sharded throughput: on a CPU host the simulated devices all
share the same cores, so the *absolute* curve is flat-ish by construction
— the bench's job there is to keep the sharded dispatch itself from
regressing, not to demonstrate CPU speedups.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

_REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")

_SCALE = 1.0


def set_scale(scale: float) -> None:
    global _SCALE
    _SCALE = scale


def _bench_json_path() -> str:
    name = "BENCH_fleet.json" if _SCALE == 1.0 else "BENCH_fleet_smoke.json"
    return os.path.join(_REPO_ROOT, name)


def measure_devices(n_dev: int, n_r: int, n_seeds: int,
                    n_events: int) -> dict:
    """Time one sharded sweep over this process's first ``n_dev`` devices."""
    import jax
    import jax.numpy as jnp
    from repro.core import Exponential, ThreePhaseKernel, run_sweep
    from repro.distributed.sharding import lane_mesh
    from repro.obs.timing import time_compiled

    kw = dict(k=10.0, n_events=n_events, key=jax.random.key(0),
              n_seeds=n_seeds, rmax=32, rng="slab",
              shard="lanes", mesh=lane_mesh(n_dev))
    out, timing = time_compiled(lambda: run_sweep(
        Exponential(1 / 12), Exponential(1 / 24), ThreePhaseKernel(),
        {"r": jnp.linspace(0.25, 4.0, n_r)}, **kw))
    timing["jobs_completed"] = int(
        jnp.sum(jnp.asarray(out["jobs_completed"])))
    return timing


# child source (CPU backend): simulate n_dev host devices, then measure.
# Parameters arrive via argv (n_devices, n_r, n_seeds, n_events); the
# result leaves as one JSON line on stdout.
_CHILD = """
import json, os, sys
args = list(map(int, sys.argv[1:5]))
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%d" % args[0])
from benchmarks.fleet_bench import measure_devices
print(json.dumps(measure_devices(*args)))
"""


def _measure_child(n_devices: int, n_r: int, n_seeds: int,
                   n_events: int) -> dict:
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)  # the child sets its own, pre-backend
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_REPO_ROOT, "src"), _REPO_ROOT]
        + [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p])
    out = subprocess.run(
        [sys.executable, "-c", _CHILD, str(n_devices), str(n_r),
         str(n_seeds), str(n_events)],
        capture_output=True, text=True, env=env, cwd=_REPO_ROOT,
        timeout=1_800)
    if out.returncode != 0:
        raise RuntimeError(
            f"fleet child ({n_devices} devices) failed:\n{out.stderr[-3000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def measure_fleet_scaling(device_counts=None, n_r: int = 32,
                          n_seeds: int = 4,
                          n_events: int | None = None) -> dict:
    """Devices × lanes scaling curve for the sharded sweep dispatch."""
    import jax

    simulated = jax.default_backend() == "cpu"
    if device_counts is None:
        if simulated:
            device_counts = (1, 2) if _SCALE < 1.0 else (1, 2, 4, 8)
        else:
            device_counts = tuple(2 ** i for i in range(
                jax.device_count().bit_length()))
    measure = _measure_child if simulated else measure_devices
    if n_events is None:
        n_events = max(2_000, int(50_000 * _SCALE))
    lanes = n_r * n_seeds
    total_events = lanes * n_events
    curve = {}
    for n_dev in device_counts:
        timing = measure(n_dev, n_r, n_seeds, n_events)
        curve[str(n_dev)] = {
            "t_run_s": timing["t_run_s"],
            "t_compile_s": timing["t_compile_s"],
            "events_per_s": total_events / timing["t_run_s"],
            "lanes_per_device": -(-lanes // n_dev),
        }
    from repro.obs.timing import provenance

    one = curve[str(device_counts[0])]
    result = {
        "device_counts": list(device_counts),
        "n_r": n_r,
        "n_seeds": n_seeds,
        "lanes": lanes,
        "n_events_per_lane": n_events,
        "total_events": total_events,
        "curve": curve,
        "events_per_s_1dev": one["events_per_s"],
        "provenance": provenance(
            seed=0, impl="xla", rng="slab", shard="lanes",
            simulated_devices=simulated),
    }
    with open(_bench_json_path(), "w") as f:
        json.dump(result, f, indent=2)
    return result


def bench_fleet_scaling():
    """Benchmark-harness entry: rows + headline (1-device sharded ev/s)."""
    res = measure_fleet_scaling()
    kind = ("simulated" if res["provenance"]["simulated_devices"]
            else res["provenance"]["device_kind"])
    rows = []
    for n_dev in res["device_counts"]:
        c = res["curve"][str(n_dev)]
        rows.append({
            "name": f"fleet/{n_dev}dev_{res['lanes']}lanes",
            "us_per_call": c["t_run_s"] * 1e6,
            "derived": (
                f"{res['lanes']} lanes × {res['n_events_per_lane']} ev on "
                f"{n_dev} {kind} device(s): {c['events_per_s']:.0f} ev/s "
                f"(compile {c['t_compile_s']:.2f}s, "
                f"{c['lanes_per_device']} lanes/device)"),
        })
    return rows, res["events_per_s_1dev"]


if __name__ == "__main__":
    if "--smoke" in sys.argv:
        set_scale(0.1)
    rows, headline = bench_fleet_scaling()
    for row in rows:
        print(f"{row['name']},{row['us_per_call']:.0f},{row['derived']}")
    print(f"headline events_per_s_1dev={headline:.0f}")
