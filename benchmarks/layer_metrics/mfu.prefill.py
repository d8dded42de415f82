"""Model step: FLOPs the tokens of the window require (benchmarks/roofline/
decoder_step.py) over the window and the peak of the chips used."""
from benchmarks.readers import mfu as read  # noqa: F401
