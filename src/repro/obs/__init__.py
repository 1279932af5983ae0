"""Observability layer: in-loop round telemetry, span tracing, metrics.

Zero-overhead-when-disabled by construction: every hook in the training
and serving paths is guarded by a *Python* flag checked at trace/build
time, so with :class:`ObsConfig` ``enabled=False`` (or no config at all)
the compiled programs are identical to a repo without this package —
enforced bit-for-bit by ``tests/test_obs.py`` for all four backends.

Modules:

  * :mod:`repro.obs.config`    — :class:`ObsConfig`, the single switch.
  * :mod:`repro.obs.telemetry` — :class:`RoundTelemetry` traced scalars
    computed inside the fused round step, the regret-tracking scan carry,
    and the JSONL round-event schema.
  * :mod:`repro.obs.sinks`     — pluggable event sinks (jsonl/csv/memory).
  * :mod:`repro.obs.trace`     — host spans to the profiler trace and a
    JSONL log.
  * :mod:`repro.obs.hist`      — HDR-style latency histograms shared by
    the serving engine, the serving bench and the examples.
  * :mod:`repro.obs.prom`      — Prometheus text exposition + parser.
  * :mod:`repro.obs.httpd`     — stdlib ``/metrics`` endpoint.
  * :mod:`repro.obs.check`     — CLI validating an emitted artifact dir.
"""
from repro.obs.config import ObsConfig
from repro.obs.hist import LatencyHistogram
from repro.obs.sinks import CsvSink, InMemorySink, JsonlSink, Sink
from repro.obs.telemetry import (
    TELEMETRY_FIELDS, RoundTelemetry, TelemetryState, rows_to_events,
    telemetry_round, telemetry_state_init, validate_round_event,
)
from repro.obs.trace import NullTracer, Tracer, install_tracer, span

__all__ = [
    "ObsConfig", "LatencyHistogram",
    "Sink", "InMemorySink", "JsonlSink", "CsvSink",
    "TELEMETRY_FIELDS", "RoundTelemetry", "TelemetryState",
    "telemetry_state_init", "telemetry_round", "rows_to_events",
    "validate_round_event",
    "Tracer", "NullTracer", "install_tracer", "span",
]
