"""Telemetry layer: distributed tracing + cluster metrics.

Reference analog: the reference engine's ``io.opentelemetry`` span
instrumentation (``tracing/TrinoAttributes``), the JMX/metrics
exposition surface, and the ``system.runtime`` introspection tables.
Three integrated pieces, all dependency-free:

- ``tracing``: Tracer/Span core with W3C-traceparent-style dict
  context, OTLP export and span-timeline analysis (span tree,
  critical path);
- ``metrics``: process-local counter/gauge/histogram registry with
  Prometheus text exposition and coordinator-side aggregation of
  heartbeat-piggybacked worker snapshots;
- ``connectors/system.py`` (outside this package) serves both as
  ``system.runtime.{queries,tasks,metrics}`` SQL tables.
"""

from .metrics import (ClusterMetrics, MetricsRegistry, merge_families,
                      process_families, relabel, render_prometheus)
from .tracing import (NULL_TRACER, Span, Tracer, critical_path,
                      span_tree, trace_line)

__all__ = [
    "ClusterMetrics", "MetricsRegistry", "merge_families",
    "process_families", "relabel", "render_prometheus",
    "NULL_TRACER", "Span", "Tracer", "critical_path", "span_tree",
    "trace_line",
]
