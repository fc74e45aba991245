"""pio_tpu.obs — dependency-free observability subsystem.

Three pillars (ISSUE 1; the reference exposes JSON request counts only —
SURVEY.md §5 observability row):

- **Metrics registry** (:mod:`pio_tpu.obs.metrics`): Counter, Gauge and
  fixed-bucket Histogram types with labels and proper ``# HELP``/``# TYPE``
  Prometheus text exposition, replacing the bespoke per-server stat
  classes and hand-rolled exposition lines.
- **Stage tracing** (:mod:`pio_tpu.obs.tracing`): a lightweight
  context-manager tracer over the single monotonic clock, with a ring
  buffer of recent traces surfaced as ``GET /traces.json``.
- **Cross-worker aggregation** (:mod:`pio_tpu.obs.shm`): in
  SO_REUSEPORT pool serving each worker mirrors its counters/histogram
  buckets into a per-worker stripe of one mmapped segment, so a scrape
  of ANY worker reports pool-wide totals.

The ops plane on top (ISSUE 2):

- **Structured logs** (:mod:`pio_tpu.obs.slog`): every record rendered
  as one-line JSON carrying the trace id of the enclosing span (the
  tracer publishes a contextvar), a bounded ring behind
  ``GET /logs.json``, and ``pio_tpu_log_messages_total`` volume counters.
- **Health probes** (:mod:`pio_tpu.obs.health`): named liveness
  (``/healthz`` — heartbeats, critical threads) and readiness
  (``/readyz`` — engine deployed, storage reachable, pool stripe
  attached) check registries.
- **SLO engine** (:mod:`pio_tpu.obs.slo`): declared objectives
  (``p99=50ms:99.9``) evaluated against the live counters/histograms as
  multi-window burn rates — ``GET /slo.json`` + ``pio_tpu_slo_*`` gauges.

Plus :mod:`pio_tpu.obs.profile` (the opt-in ``PIO_TPU_PROFILE=dir`` JAX
profiler hook), :mod:`pio_tpu.obs.promparse` (a small text-format
parser shared by tests, the fleet aggregator and the dashboard) and
:mod:`pio_tpu.obs.trainwatch` (the training telemetry plane — step
stream, ``/train.json`` progress, run ledger) and
:mod:`pio_tpu.obs.devicewatch` (the device telemetry plane — live HBM
accounting, compile attribution, ``/device.json``).

``monotonic_s`` is THE process-wide monotonic clock for durations —
serving paths used to mix ``time.monotonic()`` and
``time.perf_counter()``; every timing site now goes through this one
source (``perf_counter``: monotonic per the stdlib contract, and the
highest-resolution clock CPython offers for intervals).
"""

from __future__ import annotations

from pio_tpu.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    REGISTRY,
    RequestWindow,
    escape_help,
    escape_label_value,
    monotonic_s,
)
from pio_tpu.obs import devicewatch, trainwatch
from pio_tpu.obs.health import Heartbeat, HealthMonitor
from pio_tpu.obs.hotpath import hotpath_payload
from pio_tpu.obs.slo import SLOEngine, SLObjective, parse_duration_s, parse_slo
from pio_tpu.obs.tracing import (
    TRACE_HEADER,
    Trace,
    Tracer,
    active_span,
    active_trace,
    add_active_span,
    format_trace_header,
    parse_trace_header,
)

__all__ = [
    "DEFAULT_LATENCY_BUCKETS",
    "Counter",
    "Gauge",
    "HealthMonitor",
    "Heartbeat",
    "Histogram",
    "MetricsRegistry",
    "REGISTRY",
    "RequestWindow",
    "SLOEngine",
    "SLObjective",
    "TRACE_HEADER",
    "Trace",
    "Tracer",
    "active_span",
    "active_trace",
    "add_active_span",
    "devicewatch",
    "escape_help",
    "escape_label_value",
    "format_trace_header",
    "hotpath_payload",
    "monotonic_s",
    "parse_trace_header",
    "parse_slo",
    "parse_duration_s",
    "trainwatch",
]
