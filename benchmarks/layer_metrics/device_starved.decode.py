"""Engine: percent of the window over which the engine KNEW the device had
nothing to run while the server had work — the ``starved`` spans of the
program's device-queue row (``SpanTracer``; from a blocking read that left no
program call in flight to where the next dispatch begins). A LOWER bound on the
device's idle time, over the whole window and on the engine's own clock."""
from benchmarks.queue_readers import device_starved as read  # noqa: F401
