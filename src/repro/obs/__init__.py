"""Runtime observability: metrics registry + comm-span tracing.

The sensor layer of the plan runtime: a process-wide thread-safe metrics
registry (``metrics.py``), a nestable span tracer (``trace.py``) whose
every span goes to the JAX profiler as a ``TraceAnnotation``, on the
profiler's clock beside the device ops, and to an in-process wall-clock
ring buffer with Chrome-trace/Perfetto export, the canonical metric/span
name tables (``names.py`` — cross-checked against docs/ARCHITECTURE.md by
a tier-1 test), and a dump CLI (``python -m repro.obs.dump``).

Instrumented layers: ``sched/executor`` (plan spans + wire bytes/ratio per
kind, fed from the consolidated WireReports), ``sched/cache``
(hit/miss/eviction gauges + cache events), ``serve/engine``
(admission/prefill/decode and weight-ingest spans, queue depth,
tokens/step), ``sync/engine`` (publish/encode spans, the codec,
wire-to-host and checksum spans of each update, delta-vs-full counts,
exception-list occupancy, per-replica version lag), ``p2p/engine`` and
``runtime/fault_tolerance`` (stage/step spans + latency histograms),
``kernels.record_fallback`` (labeled counter mirror), ``core/integrity``
(wire CRC bytes, read in place or copied).

Env knobs:
  * ``REPRO_OBS=0``       — every instrumentation call becomes a near-zero
    cost no-op (shared singletons, no allocation);
  * ``REPRO_TRACE_DIR``   — default Chrome-trace export directory;
  * ``REPRO_OBS_SPAN_CAP`` — span ring-buffer capacity (default 65536).
"""
from repro.obs.config import enabled, set_enabled
from repro.obs.metrics import (DEFAULT_TIME_BUCKETS, NOOP_METRIC,
                               MetricsRegistry, registry, snapshot)
from repro.obs.names import METRICS, SPANS, SPECS, MetricSpec, metric
from repro.obs.trace import (NOOP_SPAN, SpanRecord, SpanTracer, clear_spans,
                             export_chrome_trace, instant, span, spans,
                             trace_dir, tracer)

__all__ = [
    "DEFAULT_TIME_BUCKETS", "METRICS", "MetricSpec", "MetricsRegistry",
    "NOOP_METRIC", "NOOP_SPAN", "SPANS", "SPECS", "SpanRecord",
    "SpanTracer", "clear_spans", "enabled", "export_chrome_trace",
    "instant", "metric", "registry", "reset", "set_enabled", "snapshot",
    "span", "spans", "trace_dir", "tracer",
]


def reset() -> None:
    """Drop all recorded metrics and buffered spans (run isolation)."""
    registry().reset()
    clear_spans()
