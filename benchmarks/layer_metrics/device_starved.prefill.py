"""Engine: percent of the window over which the engine KNEW the device had
nothing to run while the server had work — the ``starved`` spans of the
program's device-queue row (``SpanTracer``). A LOWER bound on the device's
idle time; what the load leaves idle is ``no_work.prefill``'s."""
from benchmarks.queue_readers import device_starved as read  # noqa: F401
