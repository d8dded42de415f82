"""Kernels: causal attention of the prefill chunks against the paged cache,
share of its roofline (benchmarks/roofline/prefill_attention.py)."""
from benchmarks.readers import kernel_roofline, traced_steps
from benchmarks.roofline import prefill_attention as work

# As in paged_attention_roofline.py: the chunk's attention is the nameless
# custom-call whose output is [1, KV, rep*C, D] and whose first operands are
# the s32 [1, M] block table and the s32 [1] chunk start (PR 23, by hand).
PATTERNS = [r"paged_prefill_attention",
            r"= \w+\[1,\d+,\d+,\d+\]\S* custom-call\(s32\[1,\d+\]\S* %\S+ s32\[1\]"]


def read(run):
    cfg = run["config"]
    D = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    chunks = [c for s in traced_steps(run) for c in s["prefill_chunks"]]
    L, nh, nkv = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    return kernel_roofline(run, PATTERNS, work.flops(nh, D, chunks, L),
                           work.nbytes(nh, nkv, D, chunks, L))
