"""``repro.obs`` — the observability subsystem.

Three layers, one axis: :class:`Telemetry` is the static descriptor the
engine entry points accept as ``telemetry=`` (the fifth dispatch axis,
after policy kernel / scenario loop / executor / RNG stream).  With it
set, sims and sweeps additionally return streaming wait/cost quantile
sketches, event-type counters, and per-pool/per-region defect/resume
counts per grid point — accumulated on-device in the same float32 window
blocks as the base stats, through all three executors.  ``telemetry=None``
(the default) compiles the identical program as before the axis existed:
zero cost, bitwise-reproduced stats (frozen in tests/test_obs.py).

* :mod:`repro.obs.stats` — device accumulators + host summaries.
* :mod:`repro.obs.shocks` — shock/degradation counters for the
  environment-timeline axis (``env=``): boundaries crossed, storms /
  blackouts / spikes entered, shock dwell times, degraded admissions.
* :mod:`repro.obs.survival` — the survival ledger for the work axis
  (``work=``): job-level finished/on-time/missed counters with frozen
  identities, work lost/recomputed to rollbacks, checkpoints taken,
  and safety-net panic entries.
* :mod:`repro.obs.trace` — event tracing (device rings / host recorder)
  and the Chrome/Perfetto exporter.
* :mod:`repro.obs.timing` — compile-vs-steady timing, BENCH provenance
  stamps, profiler trace scopes, the entry points' phase spans and the
  compile counter.
"""
from .shocks import (ENV_INT_STATS, EnvWindowStats, env_merge,
                     env_reduce, env_update, env_zeros, summarize_env)
from .survival import (SURVIVAL_INT_STATS, SurvivalWindowStats,
                       summarize_survival, survival_merge, survival_reduce,
                       survival_update, survival_zeros)
from .stats import (EVENT_TYPES, TEL_INT_STATS, Telemetry,
                    TelemetryWindowStats, sketch_quantile,
                    summarize_telemetry, telemetry_merge, telemetry_reduce,
                    telemetry_update, telemetry_zeros)
from .timing import annotate, compile_count, provenance, time_compiled
from .trace import (TraceRecorder, device_trace_records, to_perfetto,
                    write_perfetto)

__all__ = [
    "ENV_INT_STATS",
    "EVENT_TYPES",
    "EnvWindowStats",
    "SURVIVAL_INT_STATS",
    "SurvivalWindowStats",
    "TEL_INT_STATS",
    "Telemetry",
    "TelemetryWindowStats",
    "TraceRecorder",
    "annotate",
    "compile_count",
    "device_trace_records",
    "env_merge",
    "env_reduce",
    "env_update",
    "env_zeros",
    "summarize_env",
    "summarize_survival",
    "survival_merge",
    "survival_reduce",
    "survival_update",
    "survival_zeros",
    "provenance",
    "sketch_quantile",
    "summarize_telemetry",
    "telemetry_merge",
    "telemetry_reduce",
    "telemetry_update",
    "telemetry_zeros",
    "time_compiled",
    "to_perfetto",
    "write_perfetto",
]
