"""Kernels: the full layers' attention — the decode rows' walk of their
blocks and the prompt chunks' attention over everything before them —
share of its roofline. Work from the positions the full layers attended
over the traced steps — the step records' decode contexts and prompt chunks
(benchmarks/roofline/window_moe_attention.py); time = device time of the
events traced under the program's ``full_attend`` scope."""
from benchmarks.window_moe_readers import attention_roofline


def read(run):
    return attention_roofline(run, "full")
