"""How late the load generator ran: 95th percentile of submit time minus due
time over the requests due in the window (benchmark clock). A starved
generator must not read as a fast server."""
from benchmarks.readers import gen_late_p95_ms as read  # noqa: F401
