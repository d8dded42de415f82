"""Scheduler: of the window's ``prefill_chunk`` spans, the percent that begin
inside an engine-row ``decode_dispatch`` span — the chunk rode in the decode
trip's program call, one read of the weights for the tick — and not in a call
of its own (``serving_prefill_chunks_fused`` / ``_alone`` count the same)."""
from benchmarks.queue_readers import fused_chunk_share as read  # noqa: F401
