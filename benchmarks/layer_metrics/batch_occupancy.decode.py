"""Scheduler: ``load_metrics()`` slots occupied over slots total, read after
every ``step()``, mean over the window."""
from benchmarks.readers import batch_occupancy as read  # noqa: F401
