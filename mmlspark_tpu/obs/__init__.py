"""Unified observability: metrics registry, Prometheus exposition, tracing.

Three pillars (docs/observability.md):

  - ``obs.metrics``  — MetricsRegistry (thread-safe counters/gauges/
    histograms with label sets) + the Prometheus text-format writer served
    at ``/_mmlspark/metrics`` on every ServingServer and RoutingFront.
  - ``obs.bridge``   — scrape-time adapters folding the pre-existing stats
    surfaces (IngestStats, LatencyStats, CompileCache, executor timelines,
    circuit breakers) into the registry, so ``/_mmlspark/stats`` and
    Prometheus report from one source of truth.
  - ``obs.trace``    — span context propagated across HTTP hops via the
    ``X-MMLSpark-Trace`` header (deadline-header pattern), with JSONL and
    Perfetto exporters and head-based sampling.
  - ``obs.perf``     — performance attribution: per-segment XLA cost
    analytics (``extract_cost`` at CompileCache miss time), roofline
    achieved-vs-bound ratios with dominant-bottleneck labels, device
    memory telemetry, and SLO burn-rate tracking
    (``SLOConfig``/``SLOTracker``).
"""

from .metrics import (COMPILE_BUCKETS, Counter, DEFAULT_BUCKETS, Gauge,
                      Histogram, MetricFamily, MetricsRegistry,
                      SERVING_LATENCY_BUCKETS, Sample, TrainRecorder,
                      default_registry, set_default_registry)
from .trace import (Span, SpanContext, TRACE_HEADER, Tracer, batch_context,
                    current_batch, default_tracer, parse_trace_header,
                    set_default_tracer)
from .perf import SLOConfig, SLOTracker, attribute_segments, extract_cost
from . import bridge
from . import perf

__all__ = ["COMPILE_BUCKETS", "Counter", "DEFAULT_BUCKETS", "Gauge",
           "Histogram", "MetricFamily", "MetricsRegistry",
           "SERVING_LATENCY_BUCKETS", "SLOConfig", "SLOTracker", "Sample",
           "Span", "SpanContext", "TRACE_HEADER", "Tracer", "TrainRecorder",
           "attribute_segments", "batch_context", "bridge", "current_batch",
           "default_registry", "default_tracer", "extract_cost",
           "parse_trace_header", "perf", "set_default_registry",
           "set_default_tracer"]
