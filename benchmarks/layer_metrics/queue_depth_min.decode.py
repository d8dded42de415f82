"""Scheduler: least ``load_metrics()`` queue depth, read after every
``step()``, over the window. The room a capacity cell has left: it shrinks
with every gain, and at 0 ``serve_tok_s`` is no longer a capacity."""
from benchmarks.readers import queue_depth_min as read  # noqa: F401
