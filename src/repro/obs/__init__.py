"""repro.obs — unified telemetry plane (stdlib-only).

Three pieces, threaded through the whole serving stack:

* :mod:`repro.obs.metrics` — ``MetricsRegistry`` of typed counters /
  gauges / fixed-bucket mergeable histograms with Prometheus-style text
  exposition and JSON snapshots; existing stat blocks register scrape
  collectors so they become views over one registry.
* :mod:`repro.obs.trace` — ``Tracer`` producing per-request span trees
  (queue_wait → admission → prefill → decode → harvest, with nested
  lock_wait, route, retrieval and tokenize) and host spans
  (``Tracer.span``: pump, routing, submit, engine step; each also a
  profiler annotation) with an injectable clock, bounded buffers and
  seeded sampling, and Chrome trace-event export.  ``NULL_TRACER`` is
  the zero-overhead disabled path.
* :mod:`repro.obs.attribution` — per-request stage breakdowns whose
  top-level stages sum to end-to-end latency, aggregated so SLO
  burn-rate reports can name the dominant stage.
"""
from repro.obs.attribution import (KINDS, NESTED, STAGES, TOP_LEVEL,
                                   RequestBreakdown, StageAttribution)
from repro.obs.metrics import (DEFAULT_BUCKETS_MS, Counter, Gauge,
                               Histogram, MetricsRegistry)
from repro.obs.trace import (NULL_TRACER, HostSpan, NullTracer, Span,
                             Tracer)

__all__ = [
    "KINDS", "NESTED", "STAGES", "TOP_LEVEL", "RequestBreakdown",
    "StageAttribution", "DEFAULT_BUCKETS_MS", "Counter", "Gauge",
    "Histogram", "MetricsRegistry", "NULL_TRACER", "HostSpan",
    "NullTracer", "Span", "Tracer",
]
