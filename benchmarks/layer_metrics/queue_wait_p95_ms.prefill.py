"""Scheduler: 95th percentile of the program's own ``queued`` spans
(``SpanTracer``; telemetry is on in the traced run only)."""
from benchmarks.readers import queue_wait_p95_ms as read  # noqa: F401
