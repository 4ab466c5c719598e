"""repro.obs -- unified telemetry: tracing, metrics, health.

One measurement substrate instead of four instrumentation dialects
(solver ``history`` dicts, ``ServeMetrics``, ``Comm.wire_bytes``,
provenance stamps).  Where a step's device time goes -- the local
kernel, the declared collectives -- is read from a device profile, in
which the solver's ``repro.*`` spans and ``repro.comm.<name>`` scopes
mark it.

Modules:
  * ``trace``   -- :class:`Tracer`: nestable spans with an injectable
                   clock, thread-safe, near-zero overhead when disabled
                   (``NULL_TRACER``); exports Chrome-trace JSON
                   (chrome://tracing / Perfetto) and a JSONL event log;
                   every span is also a ``jax.profiler`` TraceAnnotation,
                   so it appears in device profiles, and
                   ``PROFILER_TRACER`` writes only those annotations
  * ``metrics`` -- :class:`Registry` of labelled counters / gauges /
                   histograms with one ``snapshot()`` schema shared by
                   every exporter; absorbs the legacy percentile
                   helpers
  * ``serve``   -- :class:`RequestMetrics`: the serving engine's
                   request-lifecycle bookkeeping (tok/s, TTFT, latency
                   percentiles) written through a Registry; the legacy
                   ``repro.serve.metrics.ServeMetrics`` is a deprecated
                   shim over it
  * ``recorder``-- :class:`FlightRecorder`: bounded ring-buffer tracer
                   (drop-oldest, O(capacity) memory) for the services
                   that run indefinitely, with atomic postmortem
                   bundles (``dump`` / ``crash_guard`` /
                   :func:`load_bundle`)
  * ``health``  -- declarative :class:`HealthRule` catalog over the
                   registry (divergence, gap stall, staleness, queue
                   shed, fleet starvation) and the
                   :class:`HealthMonitor` that evaluates them, records
                   verdicts as metrics, and edge-triggers recorder
                   dumps on CRIT
  * ``export``  -- Prometheus text-format rendering of a registry
                   snapshot (:func:`render_prometheus`) and its
                   validating inverse (:func:`parse_prometheus_text`)
  * ``http``    -- :class:`ObsServer`: stdlib-only background HTTP
                   endpoint with ``/metrics`` (Prometheus),
                   ``/healthz`` (503 on CRIT), and ``/varz``

Nothing in this package imports ``repro.core`` or ``repro.serve`` --
the observability layer sits below both and is threaded through them.
"""
from .export import parse_prometheus_text, render_prometheus
from .health import (CRIT, OK, WARN, HealthEvent, HealthMonitor, HealthRule,
                     fleet_rules, online_rules, rule_divergence,
                     rule_fleet_starvation, rule_gap_stall, rule_queue_shed,
                     rule_staleness, rule_version_lag, serve_rules,
                     solver_rules)
from .http import ObsServer
from .metrics import Counter, Gauge, Histogram, Registry, percentiles
from .recorder import BUNDLE_SCHEMA, FlightRecorder, load_bundle
from .serve import RequestMetrics
from .trace import (NULL_TRACER, PROFILER_TRACER, NullTracer, ProfilerTracer,
                    Tracer, as_tracer)

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "percentiles",
    "RequestMetrics",
    "NULL_TRACER", "NullTracer", "PROFILER_TRACER", "ProfilerTracer",
    "Tracer", "as_tracer",
    "BUNDLE_SCHEMA", "FlightRecorder", "load_bundle",
    "OK", "WARN", "CRIT", "HealthEvent", "HealthRule", "HealthMonitor",
    "rule_divergence", "rule_gap_stall", "rule_staleness",
    "rule_version_lag", "rule_queue_shed", "rule_fleet_starvation",
    "solver_rules", "online_rules", "serve_rules", "fleet_rules",
    "render_prometheus", "parse_prometheus_text",
    "ObsServer",
]
