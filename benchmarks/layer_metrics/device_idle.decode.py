"""Device: 1 - union of device-op intervals over the traced window."""
from benchmarks.readers import device_idle as read  # noqa: F401
