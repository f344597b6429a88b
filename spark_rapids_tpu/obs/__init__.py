"""Query-level observability: spans, event log, metrics registry.

The reference accelerator treats observability as a subsystem in its
own right — leveled ``GpuMetric`` accumulators on every operator
(GpuExec.scala:36-188), NVTX ranges (NvtxWithMetrics.scala), Spark's
event log consumed by an offline profiling tool. This package is the
TPU rebuild's counterpart, split the same way:

- :mod:`.trace` — Dapper-style spans (query → stage → task → operator)
  with monotonic timestamps, exportable as Chrome-trace (catapult)
  JSON.
- :mod:`.events` — a structured JSONL event log in the
  Spark-history-server mold (QueryStart/End, StageSubmitted/Completed,
  TaskEnd, SpillToHost/Disk, FetchFailed, RetryAttempt,
  CorruptionDetected, FaultInjected, ShuffleWrite...), emitted from
  the session, mesh executor, cluster runtime, shuffle manager, spill
  framework, retry framework, and fault harness.
- :mod:`.registry` — aggregation of the per-operator ``Metric``
  accumulators into per-query summaries, gated by ``srt.metrics.level``
  (ESSENTIAL/MODERATE/DEBUG), plus bounded log-bucketed histograms
  (task time, shuffle block size, fetch latency...) and a
  Prometheus-style text snapshot with p50/p90/p99.
- :mod:`.resource` — an optional background sampler
  (``srt.obs.resource.intervalMs``) recording RSS, device memory,
  spill/fetch/prefetch occupancy as periodic ResourceSample events.

Design contract (same discipline as the unarmed ``fault_point`` sites):
**zero overhead when disabled.** Every hook threaded through the hot
paths is a module-global ``None`` check when no sink/tracer is
installed — no event sink is created, no span objects are allocated,
no per-batch work happens. ``tools/profile_report.py`` turns an event
log back into a per-query report offline.
"""

from . import events, registry, resource, trace  # noqa: F401
