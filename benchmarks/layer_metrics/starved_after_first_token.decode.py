"""Engine: the part of ``device_starved.decode`` whose span begins where an
engine-row ``first_token_wait`` ends — the first-token sync: the host waited
for a final chunk's whole program call with nothing queued behind it, and the
device then waits for the host's share of the next dispatch. A LOWER bound."""
from benchmarks.queue_readers import (  # noqa: F401
    starved_after_first_token as read)
