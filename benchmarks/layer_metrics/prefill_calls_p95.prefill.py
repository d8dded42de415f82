"""Scheduler: 95th percentile over the window's ticks of the chunk-prefill
programs dispatched inside the tick (``prefill_chunk`` spans begun inside the
``tick`` span): each re-reads the weights while the decoding requests wait."""
from benchmarks.span_readers import prefill_calls_p95 as read  # noqa: F401
