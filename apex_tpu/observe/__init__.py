"""apex_tpu.observe — unified trace/metrics runtime.

One telemetry choke point for the whole library:

- :mod:`registry` — thread-safe counters/gauges/histograms + a
  structured JSONL event log (schema-versioned, monotonic timestamps).
- :mod:`spans` — ``span("ckpt.save")`` context manager emitting both
  the event log and ``jax.profiler.TraceAnnotation``.
- :mod:`telemetry` — the jit-safe on-device step accumulator carried in
  ``StepState.telem`` (the one submodule allowed inside traced code).
- :mod:`watchdog` — heartbeat thread firing a typed stall diagnostic.
- :mod:`catalog` — the documented name → meaning table for metric
  consumers (dashboards, bench stages, tests).

Everything except :mod:`telemetry` is host-side only; calls reachable
from jit-traced code are flagged by the OBS-IN-JIT lint rule.
"""
from .catalog import CATALOG, describe
from .registry import (SCHEMA_VERSION, Counter, Gauge, Histogram,
                       MetricsRegistry, counter, event, events, gauge,
                       get_registry, histogram)
from .spans import last_span, span
from .telemetry import StepTelemetry, accumulate, init_telemetry
from .watchdog import STALL_HINT, StallWatchdog, heartbeat, last_heartbeat

__all__ = [
    "SCHEMA_VERSION", "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "counter", "gauge", "histogram", "event", "events", "get_registry",
    "span", "last_span",
    "StepTelemetry", "init_telemetry", "accumulate",
    "StallWatchdog", "heartbeat", "last_heartbeat", "STALL_HINT",
    "CATALOG", "describe",
]
