"""Scope Observatory: unified tracing + metrics across the DSE and executor.

See :mod:`repro.obs.trace` (span tracer, Chrome trace-event export),
:mod:`repro.obs.metrics` (counters / gauges / histograms / time-weighted
series), and :mod:`repro.obs.dashboard` (self-contained HTML rendering of
timelines, sparklines, and explain() breakdowns).  Front doors elsewhere:
``SearchOptions(trace=...)``, ``Solution.serve(tracer=...)``, and
``python -m repro solve/serve --trace ... --dashboard ...``.
"""
from .dashboard import render_dashboard, write_dashboard
from .metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NULL_METRICS,
    NullRegistry,
    TimeSeries,
)
from .trace import (
    NULL_TRACER,
    NullTracer,
    Tracer,
    current_tracer,
    use_tracer,
    validate_chrome_trace,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "NULL_METRICS",
    "NULL_TRACER",
    "NullRegistry",
    "NullTracer",
    "TimeSeries",
    "Tracer",
    "current_tracer",
    "render_dashboard",
    "use_tracer",
    "validate_chrome_trace",
    "write_dashboard",
]
