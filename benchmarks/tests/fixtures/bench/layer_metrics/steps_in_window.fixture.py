"""A per-layer metric that only the fixture has: shows that a reader is found
by its name alone, with no edit to any file that was there."""
from benchmarks.readers import window_steps


def read(run):
    return float(len(window_steps(run)))
