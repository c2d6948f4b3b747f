"""Unified observability: metrics registry, request tracing, structured logs.

The serve/runtime stack grew counters in fragments — ``Scheduler.stats()``,
the plan cache's hit/miss/trace counts, ``ServeEngine.compile_stats()`` /
``kv_stats()``, ad-hoc ``log=`` lambdas.  This package gives them one
shared schema and one way out of the process:

  * :mod:`repro.obs.metrics` — a process-wide registry of counters /
    gauges / fixed-bucket histograms under stable dotted names
    (``plan_cache.hits``, ``kv.blocks_in_use``, ``serve.ttft_s``,
    ``runtime.backend_dispatch{backend=...}``), default-off and zero-cost
    when off; existing counter owners feed it through snapshot-time
    collectors.
  * :mod:`repro.obs.trace` — per-request span timelines and lexical
    ``profile_scope`` spans (scheduler rounds, engine steps) recorded on
    the scheduler's injectable clock (deterministic under ``ManualClock``),
    mirrored as ``jax.profiler`` annotations anchored to that clock, and
    exported as JSONL or Chrome-trace JSON.
  * :mod:`repro.obs.logging` — one leveled structured logger
    (``REPRO_LOG_LEVEL``) replacing the ad-hoc ``log=`` lambdas, with the
    bare-callable back-compat path preserved.
  * :mod:`repro.obs.exposition` — Prometheus text format, JSON snapshot,
    and a stdlib HTTP ``/metrics`` server.

Metric names, the span taxonomy and the exposition formats are documented
in ``docs/observability.md``.

    from repro import obs
    obs.enable()
    obs.REGISTRY.counter("serve.submitted").inc()
    obs.REGISTRY.histogram("serve.ttft_s").observe(0.042)
    print(obs.prometheus_text())
"""

from .logging import (
    ENV_LOG_LEVEL_VAR,
    LEVELS,
    Logger,
    as_logger,
    get_logger,
)
from .exposition import (
    dump_metrics,
    parse_prometheus_text,
    prometheus_text,
    start_metrics_server,
)
from .metrics import (
    DEFAULT_TIME_EDGES_S,
    ENV_OBS_VAR,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    disable,
    enable,
    enabled,
)
from .trace import (
    ClockFit,
    ProfileEvent,
    Span,
    Tracer,
    anchored_events,
    disable_profiler_annotations,
    enable_profiler_annotations,
    profile_scope,
    profiler_annotations_enabled,
    recording,
)

__all__ = [
    "ClockFit",
    "DEFAULT_TIME_EDGES_S",
    "ENV_LOG_LEVEL_VAR",
    "ENV_OBS_VAR",
    "LEVELS",
    "REGISTRY",
    "Counter",
    "Gauge",
    "Histogram",
    "Logger",
    "MetricsRegistry",
    "ProfileEvent",
    "Span",
    "Tracer",
    "anchored_events",
    "as_logger",
    "disable",
    "disable_profiler_annotations",
    "dump_metrics",
    "enable",
    "enable_profiler_annotations",
    "enabled",
    "get_logger",
    "parse_prometheus_text",
    "profile_scope",
    "profiler_annotations_enabled",
    "prometheus_text",
    "recording",
    "start_metrics_server",
]
