"""Scheduler: percent of the window over which the engine knew the device's
queue empty and the server held no request — the ``no_work`` spans of the
program's device-queue row: how far under its knee the cell is offered. A
LOWER bound on the device's idle time for want of load."""
from benchmarks.queue_readers import no_work as read  # noqa: F401
