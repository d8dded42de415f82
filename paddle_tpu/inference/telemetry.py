"""Serving telemetry — re-export shim.

The telemetry substrate (MetricsRegistry / SpanTracer / FlightRecorder /
watchdog + the ServingTelemetry facade) was promoted to the shared
top-level :mod:`paddle_tpu.telemetry` when the training tier
(TrainTelemetry, goodput accounting) started building on the same
primitives — the same promotion ``faults.py`` got when training gained
fault injection. Serving code keeps importing from here; everything is
re-exported unchanged.
"""
from ..telemetry import (DEFAULT_BUCKETS, DEVICE_QUEUE_RID,  # noqa: F401
                         DEVICE_QUEUE_SPANS, ENGINE_RID, NULL_FLIGHT,
                         NULL_PHASE, NULL_TRACER, TRAIN_RID, Counter,
                         FlightRecorder,
                         Gauge, GoodputLedger, Histogram, MetricsRegistry,
                         ServingTelemetry, SpanTracer, TrainTelemetry,
                         train_watchdog, watchdog)

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "SpanTracer", "FlightRecorder", "ServingTelemetry", "watchdog",
           "DEFAULT_BUCKETS", "NULL_TRACER", "NULL_FLIGHT"]
