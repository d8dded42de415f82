"""Engine: what the host itself takes of a tick — the program's ``tick`` span
less its ``decode_wait`` and ``first_token_wait`` children, mean over the
window's ticks (``SpanTracer``; telemetry is on in the traced run only)."""
from benchmarks.span_readers import host_ms_per_tick as read  # noqa: F401
