"""Runtime observability: metrics registry + comm-span tracing + the
wire-efficiency observatory.

The sensor layer of the plan runtime (ROADMAP item 2's recalibration loop
reads it): a process-wide thread-safe metrics registry (``metrics.py``), a
nestable span tracer (``trace.py``) whose every span goes to the JAX
profiler as a ``TraceAnnotation``, on the profiler's clock beside the
device ops, and to an in-process wall-clock ring buffer with
Chrome-trace/Perfetto export, the canonical metric/span name tables
(``names.py`` —
cross-checked against docs/ARCHITECTURE.md by a tier-1 test), and a dump
CLI (``python -m repro.obs.dump``).

On top of the sensors sits the analysis layer:

  * ``recorder.py`` — a bounded step-indexed flight recorder per metric
    series, fed automatically by ``names.metric`` (windowed stats are a
    query, not a re-instrumentation);
  * ``regret.py``  — per-bucket wire ledger (exact against
    ``roofline.summarize_wire_reports``) + offline width-regret
    re-calibration on recent payload samples;
  * ``drift.py``   — live-vs-predicted wire-ratio drift detection with
    hysteresis (``wire_drift_events_total`` + ``DriftReport``).

Instrumented layers: ``sched/executor`` (plan spans + wire bytes/ratio per
kind, fed from the consolidated WireReports, plus the per-bucket ledger),
``sched/cache`` (hit/miss/eviction gauges + cache events), ``serve/engine``
(admission/prefill/decode and weight-ingest spans, queue depth, tokens/step, KV-ship drift),
``sync/engine`` (publish/encode spans, the codec, wire-to-host, checksum
and observatory spans of each update, delta-vs-full counts, per-replica
version lag, host-path ledger + drift), ``p2p/engine`` and
``runtime/fault_tolerance`` (stage/step spans + latency histograms),
``kernels.record_fallback`` (labeled counter mirror), ``core/integrity``
(wire CRC bytes, read in place or copied).

Env knobs:
  * ``REPRO_OBS=0``       — every instrumentation call becomes a near-zero
    cost no-op (shared singletons, no allocation);
  * ``REPRO_TRACE_DIR``   — default Chrome-trace export directory;
  * ``REPRO_OBS_SPAN_CAP`` — span ring-buffer capacity (default 65536);
  * ``REPRO_OBS_RING_CAP`` — flight-recorder samples per series (1024).
"""
from repro.obs import drift, regret
from repro.obs.config import enabled, set_enabled
from repro.obs.metrics import (DEFAULT_TIME_BUCKETS, NOOP_METRIC,
                               MetricsRegistry, registry, snapshot)
from repro.obs.names import METRICS, SPANS, SPECS, MetricSpec, metric
from repro.obs.recorder import (FlightRecorder, Sample, WindowStats,
                                recorder, sparkline)
from repro.obs.trace import (NOOP_SPAN, SpanRecord, SpanTracer, clear_spans,
                             export_chrome_trace, instant, span, spans,
                             trace_dir, tracer)

__all__ = [
    "DEFAULT_TIME_BUCKETS", "FlightRecorder", "METRICS", "MetricSpec",
    "MetricsRegistry", "NOOP_METRIC", "NOOP_SPAN", "SPANS", "SPECS",
    "Sample", "SpanRecord", "SpanTracer", "WindowStats",
    "clear_observatory", "clear_spans", "drift", "enabled",
    "export_chrome_trace", "instant", "metric", "recorder", "regret",
    "registry", "reset", "set_enabled", "snapshot", "span", "sparkline",
    "spans", "trace_dir", "tracer",
]


def clear_observatory() -> None:
    """Drop the analysis layer's accumulated state — flight-recorder
    rings, drift-detector windows/events, regret payload samples — while
    KEEPING the metrics registry and span buffer (per-module attribution
    in the bench harness: counters reset with the registry elsewhere)."""
    recorder().clear()
    drift.reset()
    regret.clear_samples()


def reset() -> None:
    """Drop all recorded metrics, buffered spans, and observatory state
    (run isolation)."""
    registry().reset()
    clear_spans()
    clear_observatory()
