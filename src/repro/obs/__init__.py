"""Telemetry layer: span-level query tracing, process-wide metrics,
and exporters -- the §8 evaluation surface as a first-class subsystem.

Three modules, one pipeline:

* ``trace``   -- ``Tracer`` / ``Span`` / ring-buffered ``TraceStore``:
  one root span per executed query on every backend, per-site child
  spans on the host engine, ``match`` / ``fetch`` / ``dedup`` /
  ``filter`` child spans and structured per-join-step communication
  records on the SPMD engine (reconciling exactly with the byte
  ledger); every span mirrored into a running JAX profiler trace.
* ``metrics`` -- ``MetricsRegistry`` of counters, gauges (with change
  timelines), and fixed-bucket latency histograms (p50/p90/p99 derived
  from bucket counts, merge-able across engines).  Fed by
  ``EngineBase._bump``/``_finish`` so every ``stats().extra`` key is a
  named metric.
* ``export``  -- ``snapshot()`` JSON documents (embedded in
  ``BENCH_*.json``), ``to_prom_text()`` Prometheus exposition, and
  ``dump_spans()`` / ``spans.jsonl``.

See ``docs/observability.md`` for the span model and the metric name
catalogue.
"""
from .export import (REQUIRED_METRICS, SNAPSHOT_SCHEMA, dump_spans,
                     histogram_summary, registry_from_snapshot, snapshot,
                     to_prom_text, validate_snapshot)
from .metrics import (BYTES_BUCKETS, LATENCY_BUCKETS_SEC, Counter, Gauge,
                      Histogram, MetricsRegistry, get_registry, set_registry)
from .trace import (NULL_TRACER, Span, TraceStore, Tracer, enable_tracing,
                    get_tracer, set_tracer)

__all__ = [
    "Tracer", "Span", "TraceStore", "NULL_TRACER",
    "get_tracer", "set_tracer", "enable_tracing",
    "MetricsRegistry", "Counter", "Gauge", "Histogram",
    "get_registry", "set_registry",
    "LATENCY_BUCKETS_SEC", "BYTES_BUCKETS",
    "snapshot", "histogram_summary", "registry_from_snapshot",
    "validate_snapshot", "to_prom_text", "dump_spans",
    "SNAPSHOT_SCHEMA", "REQUIRED_METRICS",
]
