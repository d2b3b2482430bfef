"""Unified observability layer — metrics registry + tracing + adapters.

One subsystem replaces three telemetry fragments (the ``core/logging.py``
event ring, ``utils/stopwatch.py``, the hand-rolled serving counters):

- ``metrics``     — MetricsRegistry with Counter/Gauge/Histogram families,
  labels, fixed log-spaced latency buckets, Prometheus-text and JSON
  exposition, injectable clocks (tests run on FakeClock);
- ``tracing``     — contextvar-propagated Spans; the trace id rides
  ``X-MMLSpark-Trace-Id`` through io/http clients -> RoutingClient ->
  PipelineServer; finished spans feed the registry and the logging ring;
- ``instruments`` — adapters (CircuitBreaker -> state gauge / failure-rate
  gauge / transition counter + ``/stats`` exposure; SpanCollector ->
  export/drop counters + flush-latency histogram + queue-depth gauge);
- ``collector``   — bounded drop-counting span ring behind
  ``GET /trace/<id>`` / ``GET /debug/slow``, with an optional OTLP-shaped
  exporter (file sink or ``MMLSPARK_TPU_OTLP_ENDPOINT`` POST through the
  breaker-guarded io/http client).  Histograms carry exemplars linking
  bucket outliers to trace ids;
- ``federation`` / ``slo`` / ``autoscale`` — the fleet plane (ISSUE 11):
  ``MetricsFederator`` scrapes + merges every worker's ``/metrics`` into a
  ``FleetView`` (counters summed, gauges worker-labelled, histograms
  merged only on matching bucket bounds), ``SLOEngine`` evaluates
  declarative SLOs with multi-window burn rates, ``AutoscaleAdvisor``
  derives the per-class desired-replica signal — all served by
  ``TopologyService`` at ``GET /fleet/{metrics,slo,autoscale}``.

Hot paths instrumented: ``serving/server.py`` (GET /metrics, queue gauges,
queue-vs-score phase histograms, EWMA shed signal), ``serving/
distributed.py`` (per-worker request/failover/probe counters, per-worker
breakers), ``lightgbm/core.train`` (per-iteration phase timings),
``parallel/trainer.py`` (step timings).  See docs/OBSERVABILITY.md.
"""
from .metrics import (Counter, DEFAULT_LATENCY_BUCKETS, Gauge, Histogram,
                      MetricsRegistry, get_registry, set_registry)
from .tracing import (LapClock, PhaseLog, Span, TRACE_HEADER,
                      TRACEPARENT_HEADER, ambient_phase, current_span,
                      current_trace_id, format_traceparent, new_trace_id,
                      parse_traceparent, phase_log, thread_phases, trace_span)
from .instruments import (BREAKER_STATE_CODES, instrument_breaker,
                          instrument_collector)
from .collector import OTLP_ENDPOINT_ENV, SpanCollector, get_collector
from .federation import FleetView, MetricsFederator, parse_prometheus
from .slo import SLO, SLOEngine, parse_slo
from .autoscale import AutoscaleAdvisor
from .compute import (InstrumentedJit, compile_report, device_put,
                      ensure_build_info, ensure_device_memory_gauges,
                      instrumented_jit, transfer_nbytes)
from .profiling import (SamplingProfiler, ProfilerBusy, profile_window,
                        profiler_instruments)
from .flightrecorder import (FlightRecorder, flightrecorder_instruments,
                             get_flight_recorder)
from .trainwatch import (MonitorServer, TrainingRun, active_monitors,
                         active_runs, start_training_monitor,
                         training_instruments)

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "DEFAULT_LATENCY_BUCKETS", "get_registry", "set_registry",
           "Span", "TRACE_HEADER", "TRACEPARENT_HEADER", "current_span",
           "current_trace_id", "new_trace_id", "trace_span",
           "ambient_phase", "thread_phases", "LapClock", "PhaseLog",
           "phase_log",
           "parse_traceparent", "format_traceparent", "BREAKER_STATE_CODES",
           "instrument_breaker", "instrument_collector",
           "OTLP_ENDPOINT_ENV", "SpanCollector", "get_collector",
           "InstrumentedJit", "instrumented_jit", "compile_report",
           "device_put", "transfer_nbytes", "ensure_build_info",
           "ensure_device_memory_gauges",
           "FleetView", "MetricsFederator", "parse_prometheus",
           "SLO", "SLOEngine", "parse_slo", "AutoscaleAdvisor",
           "SamplingProfiler", "ProfilerBusy", "profile_window",
           "profiler_instruments", "FlightRecorder",
           "flightrecorder_instruments", "get_flight_recorder",
           "TrainingRun", "MonitorServer", "start_training_monitor",
           "training_instruments", "active_runs", "active_monitors"]
