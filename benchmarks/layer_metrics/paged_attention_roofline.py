"""Kernels: decode attention over the paged cache, share of its roofline.
Work = what the algorithm needs for the KV lengths actually attended in the
traced steps (benchmarks/roofline/paged_attention.py); time = device time of
the matching trace events."""
from benchmarks.readers import kernel_roofline, traced_steps
from benchmarks.roofline import paged_attention as work

# On the v5e an op's trace event is named by its whole HLO text, and a Pallas
# kernel is a nameless ``custom-call`` (PR 23, looked at by hand). The decode
# attention is the custom-call whose output is [B, KV, rep, D] with B > 1 rows
# and whose first operands are the s32 [B, M] block tables and the s32 [B]
# positions. A kernel that carries a name gets a first pattern of its own.
PATTERNS = [r"paged_decode_attention",
            r"= \w+\[(?!1,)(\d+),\d+,\d+,\d+\]\S* custom-call\(s32\[\1,\d+\]"
            r"\S* %\S+ s32\[\1\]"]


def read(run):
    cfg = run["config"]
    D = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    st = traced_steps(run)
    ctx = sum(s["decode_ctx"] for s in st)
    rows = sum(s["decode_rows"] for s in st)
    L, nh, nkv = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    return kernel_roofline(run, PATTERNS, work.flops(nh, D, ctx, L),
                           work.nbytes(nh, nkv, D, ctx, rows, L))
