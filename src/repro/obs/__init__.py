"""repro.obs -- the zero-perturbation telemetry plane.

Span tracing (Chrome-trace/Perfetto JSON), a metrics registry (counters /
gauges / HDR histograms -> JSONL), and the process-wide session that owns
both.  See DESIGN.md section 11 for the span model and the sync-boundary
policy; ``repro.launch.obs_report`` renders the outputs.

Import-time constraint: this package (and everything re-exported here)
is **stdlib-only** -- ``repro.data.stream`` is numpy-only by design and
imports us, so jax may only ever be looked up lazily at call time
(``trace._host_time_ok``, ``scopes``).  ``repro.obs.scopes`` maps the
compiled sweep's instructions to its named phases.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               load_jsonl)
from repro.obs.runtime import (ObsConfig, ObsSession, active, metrics_for,
                               metrics_registry, session, span, tracer,
                               tracer_for)
from repro.obs.timing import TimerResult, time_loop
from repro.obs.trace import NULL_SPAN, Span, Tracer, annotation

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "load_jsonl",
    "ObsConfig", "ObsSession", "active", "metrics_for", "metrics_registry",
    "session", "span", "tracer", "tracer_for",
    "TimerResult", "time_loop",
    "NULL_SPAN", "Span", "Tracer", "annotation",
]
