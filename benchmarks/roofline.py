"""Roofline derivation from dry-run artifacts (§Roofline of EXPERIMENTS.md).

Hardware peaks live in :data:`PEAKS`, one row per ``jax.Device.device_kind``
with its source; :func:`peaks` raises for a kind that is not in the table.
The LM dry-run compiles for the v5e production meshes, so its rows are
derived against :data:`DRYRUN_TARGET`.

Terms per (arch × shape × mesh), all in seconds per step:
  compute    = HLO_FLOPs_per_device / peak
  memory     = HLO_bytes_per_device / HBM_bw
  collective = wire_bytes_per_device / link_bw

(cost_analysis() reports per-device numbers, verified against a hand-counted
einsum; wire bytes come from the loop-aware HLO parse.)

Derived:
  bottleneck        = argmax of the three terms
  MODEL_FLOPS       = 6·N·D (dense train) / 6·N_active·D (MoE) / 2·N·D
                      (inference fwd), D = tokens processed
  useful_ratio      = MODEL_FLOPS / (HLO_FLOPs × chips)  — remat/redundancy
  mfu_bound         = MODEL_FLOPS / (chips × peak × max(term))  — the
                      roofline fraction: model-useful utilization if the
                      step ran exactly at its dominant-term bound.
"""
from __future__ import annotations

import glob
import json
import os

#: Published per-chip peaks by ``device_kind``.  "TPU v5 lite" is TPU v5e
#: (Google Cloud documentation, "TPU v5e"): 197 TFLOP/s bf16, 819 GB/s
#: HBM, 1,600 Gbit/s of chip-to-chip interconnect over 4 links (50 GB/s
#: each).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_s": 819e9,
                    "link_bytes_s": 50e9},
}

#: The chip the LM dry-run's production meshes are compiled for.
DRYRUN_TARGET = "TPU v5 lite"

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                            "artifacts")


def peaks(device_kind: str) -> dict:
    """The :data:`PEAKS` row of ``device_kind``; an unknown kind raises."""
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add its row to PEAKS with a source")
    return PEAKS[device_kind]


def model_flops(art: dict) -> float:
    cell = art["cell"]
    n_active = art["active_params"]
    if cell["kind"] == "train":
        tokens = cell["seq_len"] * cell["global_batch"]
        return 6.0 * n_active * tokens
    if cell["kind"] == "prefill":
        tokens = cell["seq_len"] * cell["global_batch"]
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * cell["global_batch"]


def derive(art: dict) -> dict:
    chips = art["chips"]
    peak = peaks(DRYRUN_TARGET)
    compute = art["flops_per_device"] / peak["flops"]
    memory = art["bytes_accessed_per_device"] / peak["hbm_bytes_s"]
    collective = (art["collectives"]["wire_bytes_per_device"]
                  / peak["link_bytes_s"])
    terms = {"compute": compute, "memory": memory, "collective": collective}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(art)
    hlo_total = art["flops_per_device"] * chips
    useful = mf / hlo_total if hlo_total else 0.0
    bound = max(terms.values())
    mfu_bound = mf / (chips * peak["flops"] * bound) if bound else 0.0
    return {
        **{k: art[k] for k in ("arch", "shape", "mesh", "chips")},
        "compute_s": compute,
        "memory_s": memory,
        "collective_s": collective,
        "bottleneck": bottleneck,
        "model_flops": mf,
        "useful_ratio": useful,
        "mfu_bound": mfu_bound,
        "peak_gib": art["memory"]["peak_bytes_estimate"] / 2**30,
        "tpu_peak_gib": art["memory"].get("tpu_peak_model", 0) / 2**30,
        "tag": art.get("tag", "baseline"),
    }


def load_all(tag: str | None = None) -> list[dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(ARTIFACT_DIR, "*.json"))):
        art = json.load(open(path))
        art_tag = art.get("tag", "baseline")
        if tag is None and art_tag != "baseline":
            continue
        if tag is not None and art_tag != tag:
            continue
        rows.append(derive(art))
    return rows


def bench_engine_roofline():
    """Sweep-engine throughput roofline from BENCH_sweep.json.

    The event loop's working set per (event × grid point) is the engine
    state + stats (~``16·rmax + 96`` bytes read+written); comparing achieved
    event throughput against the device's HBM bound (:data:`PEAKS`, by the
    bench's ``device_kind``) says how far the batched engine sits from its
    memory roofline.  A bench measured on cpu gets no roofline.  (Run
    ``benchmarks/sweep_bench.py`` first — benchmarks/run.py orders them.)
    """
    root = os.path.join(os.path.dirname(__file__), "..")
    paths = [os.path.join(root, n)
             for n in ("BENCH_sweep.json", "BENCH_sweep_smoke.json")]
    path = next((p for p in paths if os.path.exists(p)), None)
    if path is None:
        return [{"name": "engine_roofline/missing", "us_per_call": 0,
                 "derived": "BENCH_sweep.json not found; run sweep bench"}], 0.0
    r = json.load(open(path))
    if r.get("backend") == "cpu":
        return [{"name": "engine_roofline/cpu", "us_per_call": 0,
                 "derived": f"{os.path.basename(path)} was measured on cpu; "
                            f"no published peak, no roofline"}], 0.0
    state_bytes = 2 * (16 * r["rmax"] + 96)  # state+stats, read and written
    bw = peaks(r["provenance"]["device_kind"])["hbm_bytes_s"]
    bound_ev_s = bw / state_bytes
    frac = r["sweep_events_per_s"] / bound_ev_s
    rows = [{
        "name": f"engine_roofline/{r['grid_points']}pt",
        "us_per_call": 0,
        "derived": (
            f"batched {r['sweep_events_per_s']/1e6:.2f}M ev/s vs "
            f"stream-bound {bound_ev_s/1e6:.0f}M ev/s "
            f"({frac*100:.1f}% of roofline; loop path "
            f"{r['loop_events_per_s']/1e6:.2f}M ev/s; "
            f"speedup {r['speedup']:.1f}x on {r.get('backend', '?')})"
        ),
    }]
    # Pallas batched-event kernel row: same streaming bound, but a compiled
    # kernel keeps the window resident in VMEM so the HBM term amortizes
    # over the whole event block — interpret-mode numbers are parity checks,
    # not kernel speed, and are labeled as such.
    kpaths = [os.path.join(root, n) for n in
              ("BENCH_engine_kernel.json", "BENCH_engine_kernel_smoke.json")]
    kpath = next((p for p in kpaths if os.path.exists(p)), None)
    if kpath is not None:
        kr = json.load(open(kpath))
        mode = "interpret" if kr.get("interpret") else "compiled"
        ev_s = kr["single"]["pallas_events_per_s"]
        kfrac = ev_s / bound_ev_s
        rows.append({
            "name": f"engine_roofline/pallas_{kr['grid_points']}pt_{mode}",
            "us_per_call": 0,
            "derived": (
                f"pallas({mode}) {ev_s/1e6:.2f}M ev/s "
                f"({kfrac*100:.1f}% of stream-bound; "
                f"{kr['single']['pallas_speedup_x']:.2f}x vs xla executor; "
                f"market {kr['market']['pallas_events_per_s']/1e6:.2f}M "
                f"ev/s; bit_equal_ref={kr['single']['bit_equal_ref']})"
            ),
        })
    return rows, frac


def bench_roofline():
    """Emit one row per baseline cell (single-pod mesh = the §Roofline
    table; multi-pod proves the pod axis shards)."""
    rows = []
    for r in load_all():
        rows.append({
            "name": f"roofline/{r['arch']}/{r['shape']}/{r['mesh']}",
            "us_per_call": 0,
            "derived": (
                f"compute={r['compute_s']*1e3:.2f}ms "
                f"memory={r['memory_s']*1e3:.2f}ms "
                f"collective={r['collective_s']*1e3:.2f}ms "
                f"bottleneck={r['bottleneck']} "
                f"useful={r['useful_ratio']:.2f} "
                f"mfu_bound={r['mfu_bound']:.3f}"
            ),
        })
    frac = [r["mfu_bound"] for r in load_all()
            if r["mesh"] == "pod_16x16" and r["shape"] == "train_4k"]
    avg = sum(frac) / len(frac) if frac else 0.0
    return rows, avg


def markdown_table(rows: list[dict]) -> str:
    hdr = ("| arch | shape | mesh | compute (ms) | memory (ms) | "
           "collective (ms) | bottleneck | useful | MFU-bound | raw peak GiB "
           "| TPU peak GiB |")
    sep = "|" + "---|" * 11
    out = [hdr, sep]
    for r in rows:
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {r['compute_s']*1e3:.2f} | {r['memory_s']*1e3:.2f} "
            f"| {r['collective_s']*1e3:.2f} | **{r['bottleneck']}** "
            f"| {r['useful_ratio']:.2f} | {r['mfu_bound']:.3f} "
            f"| {r['peak_gib']:.1f} | {r['tpu_peak_gib']:.1f} |")
    return "\n".join(out)


if __name__ == "__main__":
    rows = load_all()
    print(markdown_table(rows))
