"""Engine: 95th percentile of the time between the ends of successive
``harvest`` spans in the window — the moments at which every decoding request
gets its next token (one decode tick a step in this cell)."""
from benchmarks.span_readers import token_gap_p95_ms as read  # noqa: F401
