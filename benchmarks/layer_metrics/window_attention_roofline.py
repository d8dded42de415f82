"""Kernels: the window layers' attention — the decode rows' walk of their
rings and the prompt chunks' attention over a ring's past — share of its
roofline. Work = what the positions attended need (benchmarks/roofline/
window_moe_attention.py, at the positions the window layers attended over
the traced steps: the program's ``serving_decode_ctx_window`` and the
prompt chunks, as drivers/serve_window_moe.py keeps them per step); time =
device time of the events traced under the program's ``window_attend``
scope."""
from benchmarks.window_moe_readers import attention_roofline


def read(run):
    return attention_roofline(run, "window")
