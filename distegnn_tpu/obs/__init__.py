"""distegnn_tpu.obs — unified observability (docs/OBSERVABILITY.md).

One substrate for every runtime:
  - ``obs.span("name")`` — the one instrumentation call: a span in the
    profiler's trace (``jax.profiler.TraceAnnotation``), in the in-memory
    ring (``obs.recent_spans()``) and, once :func:`configure` has bound a
    sink, in ``<log_dir>/obs/events.jsonl``; ``obs.event`` / ``obs.log`` —
    structured records into the same file, no-ops without a sink (and always
    under ``obs.enable: false``) (``obs/trace.py``);
  - ``Counter`` / ``Gauge`` / ``LatencyReservoir`` / ``MetricsRegistry`` —
    reusable run metrics with a JSON snapshot and a Prometheus-text renderer
    (``obs/metrics.py``; the serve stack's ``ServeMetrics`` is built on
    these);
  - JAX-runtime probes (``obs/jaxprobe.py``): a ``jax/compile`` span per
    program compiled or fetched from the persistent cache, the compile
    watcher that catches recompiles-after-warmup, device memory stats, and
    the host->device transfer byte counter;
  - declarative SLOs (``obs/slo.py``): :class:`SLOSpec` thresholds scored
    against the event stream or a live ``GET /metrics`` scrape, plus the
    :class:`SLOMonitor` rolling-window gauges the gateway exports.

Render a run: ``python scripts/obs_report.py <log_dir>/obs/events.jsonl``.
"""

from distegnn_tpu.obs.metrics import (Counter, Gauge, LatencyReservoir,
                                      MetricsRegistry, REGISTRY, get_registry,
                                      percentile)
from distegnn_tpu.obs.slo import SLOMonitor, SLOSpec
from distegnn_tpu.obs.trace import (RING_SIZE, EventWriter, SpanRecord,
                                    Tracer, clear_spans, configure,
                                    configure_from_config, event, flush,
                                    get_tracer, log, recent_spans, span,
                                    spanned)
from distegnn_tpu.obs import jaxprobe  # noqa: F401  registers the jax/compile listener

__all__ = [
    "Counter", "Gauge", "LatencyReservoir", "MetricsRegistry", "REGISTRY",
    "get_registry", "percentile",
    "RING_SIZE", "EventWriter", "SpanRecord", "Tracer", "clear_spans",
    "configure",
    "configure_from_config", "event", "flush", "get_tracer", "log",
    "recent_spans", "span", "spanned",
    "SLOMonitor", "SLOSpec",
]
