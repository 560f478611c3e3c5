"""Unified tracing, telemetry & run health (see docs/OBSERVABILITY.md).

``get_tracer()`` returns the process-wide :class:`Tracer`; the runtime,
search, and fit loops record spans/counters into it, and ``--trace-out``
exports Chrome-trace JSON readable by chrome://tracing / Perfetto and by
``tools/trace_report.py``.  ``setup_summary()`` reads what set-up cost
(the ``ff.setup`` spans and jax's compiles under them) at any level.

``get_monitor()`` returns the process-wide :class:`HealthMonitor` — the
per-step metrics stream (``--metrics-out`` JSONL), the NaN/loss-spike
detectors (``--health``), and the debug-bundle flight recorder.
"""

from flexflow_tpu.obs.health import (
    DRIFT_POLICIES,
    HEALTH_POLICIES,
    DriftDetector,
    HealthError,
    HealthMonitor,
    SpikeDetector,
    configure_monitor,
    configure_monitor_from_config,
    get_monitor,
    set_monitor,
)
from flexflow_tpu.obs.aggregate import (
    AGG_SCHEMA,
    MetricsAggregator,
    QuantileSketch,
    aggregate_streams,
)
from flexflow_tpu.obs.metrics import (
    METRICS_SCHEMA,
    MetricsStream,
    metrics_file_set,
    read_metrics,
    step_record,
)
from flexflow_tpu.obs.export import render_prometheus
from flexflow_tpu.obs.schemas import SCHEMAS
from flexflow_tpu.obs.slo import (
    ALERT_SCHEMA,
    SLOEngine,
    SLOPolicy,
    fleet_from_serve_report,
    read_alerts,
    replay_stream,
    scaling_recommendation,
)
from flexflow_tpu.obs.spans import (
    SPAN_KINDS,
    SPAN_SCHEMA,
    SpanRecorder,
    read_spans,
    span_record,
    spans_by_trace,
)
from flexflow_tpu.obs.trace import (
    CORE_COUNTERS,
    LEVELS,
    Tracer,
    configure,
    configure_from_config,
    get_tracer,
    persistent_cache_hits,
    set_tracer,
    setup_span,
    setup_summary,
)

__all__ = [
    "Tracer",
    "get_tracer",
    "set_tracer",
    "setup_summary",
    "setup_span",
    "persistent_cache_hits",
    "configure",
    "configure_from_config",
    "CORE_COUNTERS",
    "LEVELS",
    "HealthMonitor",
    "HealthError",
    "SpikeDetector",
    "DriftDetector",
    "HEALTH_POLICIES",
    "DRIFT_POLICIES",
    "get_monitor",
    "set_monitor",
    "configure_monitor",
    "configure_monitor_from_config",
    "MetricsStream",
    "METRICS_SCHEMA",
    "metrics_file_set",
    "read_metrics",
    "step_record",
    "SpanRecorder",
    "SPAN_SCHEMA",
    "SPAN_KINDS",
    "read_spans",
    "span_record",
    "spans_by_trace",
    "MetricsAggregator",
    "QuantileSketch",
    "AGG_SCHEMA",
    "aggregate_streams",
    "SCHEMAS",
    "SLOPolicy",
    "SLOEngine",
    "ALERT_SCHEMA",
    "scaling_recommendation",
    "read_alerts",
    "replay_stream",
    "fleet_from_serve_report",
    "render_prometheus",
]
