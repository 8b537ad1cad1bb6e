"""Observability — one registry, request tracing, device-cost accounting.

The serving stack's single source of truth for measurement:

* :mod:`repro.obs.registry` — ``MetricsRegistry`` with counters, gauges,
  and log-bucketed latency histograms; one-lock-consistent snapshots.
* :mod:`repro.obs.tracing` — per-request spans through the async pipeline
  (admission → linger → dispatch → device → scatter) with a bounded ring
  of recent full traces; host spans of the program's steps on the
  profiler's clock; the device stages' scope names.
* :mod:`repro.obs.profiling` — jaxpr-walking collective accountant plus
  XLA cost-analysis integration, one :class:`ExecutorCost` per compiled
  executor in the AOT grid.
* :mod:`repro.obs.export` — Prometheus-text and JSONL renderers (and the
  scrape-side parser the CI gates use).

Quickstart::

    from repro.obs import render_prometheus

    server = TableServer(table, keys, values)
    ...
    print(render_prometheus(server.metrics()))
"""
from repro.obs.export import (
    parse_prometheus,
    render_jsonl,
    render_prometheus,
    write_jsonl,
)
from repro.obs.profiling import (
    COLLECTIVE_PRIMITIVES,
    ExecutorCost,
    collective_profile,
    count_primitive,
    profile_executor,
)
from repro.obs.registry import (
    DEFAULT_BOUNDS,
    Counter,
    Gauge,
    Histogram,
    HistogramSnapshot,
    MetricsRegistry,
    RegistrySnapshot,
)
from repro.obs.tracing import (
    PHASES,
    STAGES,
    SpanRecord,
    Trace,
    Tracer,
    process_tracer,
    stage,
)

__all__ = [
    "COLLECTIVE_PRIMITIVES",
    "Counter",
    "DEFAULT_BOUNDS",
    "ExecutorCost",
    "Gauge",
    "Histogram",
    "HistogramSnapshot",
    "MetricsRegistry",
    "PHASES",
    "RegistrySnapshot",
    "STAGES",
    "SpanRecord",
    "Trace",
    "Tracer",
    "collective_profile",
    "count_primitive",
    "parse_prometheus",
    "process_tracer",
    "profile_executor",
    "render_jsonl",
    "render_prometheus",
    "stage",
    "write_jsonl",
]
