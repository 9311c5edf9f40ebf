"""Always-on, low-overhead observability layer (ISSUE 7).

The package threads one measurement substrate through the whole pipeline:

  metrics.py     lock-free per-thread metrics registry — counters, gauges,
                 fixed-bucket log-scale latency histograms with
                 p50/p95/p99/p99.9 extraction. Writers touch only a shard
                 owned by their thread (no lock, no CAS on the hot path);
                 readers sum shards at scrape time.
  tracing.py     batch tracing — a monotonically increasing batch ID minted
                 at ingress and carried through delivery, per-stage span
                 timings (accept→stage→H2D→device→sink) into per-stage
                 histograms, plus a bounded worst-N slow-batch exemplar ring
                 surfaced in statistics_report()["slow_batches"]; and the
                 stage spans (`Span`, `StageCells`) that tell wait from work
                 per thread of the served path: cumulative `stage_ms` cells
                 and `siddhi.*` events on the profiler's clock.
  prometheus.py  text-exposition rendering for GET /metrics (hand-rolled —
                 no prometheus_client dependency) + a conformance validator
                 used by tests and the CI smoke.
  profiling.py   SIDDHI_PROFILE=<dir> jax.profiler trace capture; the
                 `siddhi.*` stage spans of tracing.py show inside it.
  logs.py        SIDDHI_LOG_FORMAT=json one-line structured log records.
  slo.py         declarative objectives (@app:slo / @slo) evaluated with
                 multi-window burn rates on a virtual-clock-testable engine
                 (ISSUE 10); surfaced via statistics_report()["slo"],
                 siddhi_slo_* families, and GET /slo.
  recorder.py    flight recorder — always-on evidence rings frozen into
                 versioned diagnostic bundles on anomaly triggers (SLO
                 breach, breaker open, recovery, upgrade rollback,
                 dead-letter burst, manual POST), rate-limited + de-duped;
                 analyzed offline by `python -m siddhi_tpu.doctor`.

Gating: SIDDHI_TELEMETRY=0 turns span/histogram recording off (the <5%
overhead budget is guarded by tests/test_telemetry.py); default is ON — the
whole point is that production always has the data.
"""

from __future__ import annotations

import os

from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .recorder import SCHEMA_VERSION, FlightRecorder
from .slo import Objective, SloEngine, slo_engine_from_app
from .tracing import AppTelemetry, BatchTrace

__all__ = [
    "AppTelemetry",
    "BatchTrace",
    "Counter",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "Objective",
    "SCHEMA_VERSION",
    "SloEngine",
    "slo_engine_from_app",
    "telemetry_enabled",
]


def telemetry_enabled() -> bool:
    """Process-wide default for new apps: SIDDHI_TELEMETRY=0 disables the
    always-on span/histogram recording (overhead A/B runs flip this)."""
    return os.environ.get("SIDDHI_TELEMETRY", "1").strip() != "0"
