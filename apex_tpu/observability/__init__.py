"""apex_tpu.observability — metrics, step telemetry, goodput.

The unified telemetry layer (TorchTitan's built-in-metrics pillar,
PAPERS.md arxiv 2410.06511) over three sub-modules:

- :mod:`~apex_tpu.observability.metrics`: process-local rank-aware
  counters/gauges/histograms with labels, a JSONL time-series sidecar
  (the ``log_structured`` greppability contract) and a Prometheus text
  exporter; :class:`MetricsScope` routes the resilience/IO/serving
  retrofit counters (fallback trips, io retries, watchdog wedges,
  preemption drains, queue depth, TTFT) into a caller-owned registry.
- :mod:`~apex_tpu.observability.stepstats`: the :class:`StepStats`
  pytree riding ``make_train_step(telemetry=...)`` — loss, the grad
  norm reused from the fused clip reduction, the finite vote, the
  loss scale, param/update norms — accumulated device-side and fetched
  asynchronously (:class:`AsyncFetcher`; zero ``.item()`` in the hot
  loop — analyzer rule APX108 enforces the seam).
- :mod:`~apex_tpu.observability.goodput`: per-session wall-time
  attribution (checkpoint / restore / restart / wedge vs productive)
  whose report fractions sum to 1 across elastic restarts, plus the
  centralized model-FLOPs/MFU formulas.
- :mod:`~apex_tpu.observability.tracing`: host-side distributed
  tracing — the near-zero-overhead :func:`span` API over the run's
  host phases (data wait, step dispatch, checkpoint, serving
  admission/prefill/decode, supervisor attempts), a bounded in-memory
  ring, and JSONL + Chrome-trace/Perfetto exporters.  Spans wrap
  DISPATCH, never run inside jit: tracing on/off lowers identically
  and loss/params stay bitwise (the lowered + parity pins).
- :mod:`~apex_tpu.observability.flightrec`: the crash-forensics
  flight recorder — a fixed-size ring of recent spans + structured
  events + StepStats windows, dumped atomically on watchdog wedge,
  StepGuard abort, and preemption, so every exit-75/137 leaves a
  self-contained postmortem artifact.
- :mod:`~apex_tpu.observability.anomaly`: rolling median/MAD anomaly
  and straggler detection over step time, per-hop sync time, goodput,
  and per-lane serving latency — ``apex_anomaly_*`` counters plus
  structured alerts the supervisor's backoff consumes.

See docs/observability.md for the metric name schema, the fetch-cadence
knob, the goodput attribution table, the span naming schema, the
flight-recorder dump triggers, and the detector knobs.
"""

from apex_tpu.observability.anomaly import (
    AnomalyMonitor, RollingMadDetector,
)
from apex_tpu.observability.correlation import (
    clear_step_context, set_step_context, step_context,
)
from apex_tpu.observability.flightrec import FlightRecorder
from apex_tpu.observability.goodput import (
    GoodputAccountant, goodput_report, model_flops_per_token, param_count,
    session_progress,
)
from apex_tpu.observability.metrics import (
    MetricsRegistry, MetricsScope, get_metrics,
)
from apex_tpu.observability.stepstats import (
    AsyncFetcher, StepStats, StepTelemetry,
)
from apex_tpu.observability.tracing import (
    TracedStep, Tracer, TracingScope, new_trace_id, span,
)

__all__ = [
    "AnomalyMonitor", "AsyncFetcher", "FlightRecorder",
    "GoodputAccountant", "MetricsRegistry", "MetricsScope",
    "RollingMadDetector", "StepStats", "StepTelemetry", "TracedStep",
    "Tracer", "TracingScope", "clear_step_context", "get_metrics",
    "goodput_report", "model_flops_per_token", "new_trace_id",
    "param_count", "session_progress", "set_step_context", "span",
    "step_context",
]
