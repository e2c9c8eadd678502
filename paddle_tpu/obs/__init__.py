"""Cluster observability: telemetry registry, cross-process trace
spans, and the merged cluster timeline/report.

Three pillars (see README "Observability"):

- `obs.telemetry` — process-wide Counters/Gauges/Histograms with a
  near-free disabled path, `snapshot()`, and periodic JSONL export.
- `obs.trace` — the one bounded span buffer (`spans()`), on
  perf_counter(): profiler.RecordEvent (the program's one scoped span),
  the RPC layer's cross-process spans (ids ride the wire meta dict's
  optional `trace` field) and record_span() all land in it;
  `FLAGS_obs_dir` drains it, with FaultEvents, into one per-process
  JSONL event log.
- `obs.report` — merges per-role logs into one chrome://tracing
  timeline (clock offsets estimated from RPC midpoints, device-op
  lanes from profiler xplane captures) plus a metrics rollup.
  CLI: `python tools/obs_report.py --obs_dir ...`.

Plus the device-side performance observatory on top of them:

- `obs.perf` — compile/JIT telemetry (xla.compile spans,
  xla.compile_latency, xla.jit_cache.{hit,miss}), perf.steps and
  perf.step_latency (only where the run's own fetch waited for the
  device), hbm.* gauges read on demand, and the chips' peak table.
  Wired into Executor/ParallelExecutor; waits for nothing.
- `obs.slo` — declarative threshold rules over the registry
  (gauge levels, latency percentiles, serving rates) evaluated by a
  watchdog that emits slo.breach events.

Registry and span buffer are on between `telemetry.enable()` and
`disable()`; `FLAGS_obs_dir` enables them at import and adds the JSONL
exporters (the Supervisor plants a per-role subdir in each child's
environment). Off, every hook is one boolean read.
"""
from . import telemetry, trace, report, perf, slo

__all__ = ['telemetry', 'trace', 'report', 'perf', 'slo']
