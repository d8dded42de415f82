"""Engine: host time around every ``GenerationServer.step()`` in the window,
total over ticks (benchmark clock)."""
from benchmarks.readers import tick_ms as read  # noqa: F401
