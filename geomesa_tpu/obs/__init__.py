"""geomesa_tpu.obs: the observability layer (docs/observability.md).

Three surfaces over one substrate:

- **structured tracing** (:mod:`~geomesa_tpu.obs.trace`): a ``Span``
  context with thread-local propagation threaded through the full query
  path (planner cache probe → z-range decomposition → scheduler
  admission/queue/fused dispatch → kernel scan → decode/residue) and
  the write path (micro-flush stages, WAL append/fsync, fold slices),
  retained in a bounded ``TraceBuffer`` and exportable as Chrome
  trace-event JSON (``DataStore.dump_trace``). An always-on slow-query
  log captures span trees over ``geomesa.obs.slow.ms``. Beside the spans,
  two process-wide records of what belongs to no span: the runtime's
  stalls (``stalls``, ``stall_totals``: the collector's pauses, the
  compiler's phases) and the interpreter lock (``lock_probe``: what a
  hand-off of the lock costs, sampled by one probe thread; ``lock_cpu``:
  the CPU seconds of the program's threads by role).
- **live histograms** (:class:`~geomesa_tpu.metrics.Histogram`): the
  hot-path latencies record into fixed-log-bucket histograms, so "query
  p99 right now" reads straight off ``MetricsRegistry``.
- **SLO tracking** (:mod:`~geomesa_tpu.obs.slo`): declarative
  objectives over sliding windows with burn-rate counters, served by
  ``DataStore.slo_report()``.
- **the ops plane** (:mod:`~geomesa_tpu.obs.ops`): a dependency-free
  threaded HTTP endpoint (``DataStore.serve_ops``) exposing
  ``/metrics``, the composite ``/health`` state machine, ``/stats``,
  the debug surfaces, and a :class:`~geomesa_tpu.obs.ops.
  TelemetryRecorder` writing bounded time-series rings of key gauges
  and histogram quantiles.
- **estimate accountability** (:mod:`~geomesa_tpu.obs.accuracy`):
  every executed plan records the cost model's estimated rows next to
  the rows actually scanned; per-index error windows flag stale stats
  in ``/health`` and optionally trigger an automatic ``analyze_stats``.
"""

from geomesa_tpu.obs.accuracy import EstimateAccuracy, error_factor
from geomesa_tpu.obs.ops import (
    HealthMonitor,
    OpsServer,
    TelemetryRecorder,
    ops_report,
    stats_payload,
)
from geomesa_tpu.obs.slo import SloObjective, SloTracker, default_objectives
from geomesa_tpu.obs.trace import (
    Span,
    Trace,
    TraceBuffer,
    Tracer,
    event,
    install,
    lock_cpu,
    lock_probe,
    phase_breakdown,
    span,
    stall_totals,
    stalls,
    tracer,
)

__all__ = [
    "Span",
    "Trace",
    "TraceBuffer",
    "Tracer",
    "EstimateAccuracy",
    "HealthMonitor",
    "OpsServer",
    "SloObjective",
    "SloTracker",
    "TelemetryRecorder",
    "default_objectives",
    "error_factor",
    "event",
    "install",
    "lock_cpu",
    "lock_probe",
    "ops_report",
    "phase_breakdown",
    "span",
    "stall_totals",
    "stalls",
    "stats_payload",
    "tracer",
]
