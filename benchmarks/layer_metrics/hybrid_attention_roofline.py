"""Kernels: ALL decode-attention events of the traced steps of a model with
window, full and cross (shared-pool) attention layers — share of their
roofline. Work = bytes and FLOPs by kind for the positions attended
(benchmarks/roofline/hybrid_attention.py, from the program's
``serving_decode_ctx_window`` / ``_shared`` counters per step); time = device
time of the matching trace events."""
from benchmarks.hybrid_readers import traced_pairs
from benchmarks.readers import kernel_roofline
from benchmarks.roofline import hybrid_attention as work

# As paged_attention_roofline.py matches them: a nameless custom-call whose
# output is [B, KV, rep, D] with B > 1 rows and whose first operands are the
# s32 [B, M] block tables (M = the table width, or the ring of a window
# layer) and the s32 [B] positions.
PATTERNS = [r"paged_decode_attention",
            r"= \w+\[(?!1,)(\d+),\d+,\d+,\d+\]\S* custom-call\(s32\[\1,\d+\]"
            r"\S* %\S+ s32\[\1\]"]


def read(run):
    st = traced_pairs(run)
    if not st:
        return None
    cw = sum(h["decode_ctx_window"] for _, h in st)
    cs = sum(h["decode_ctx_shared"] for _, h in st)
    rows = sum(h["decode_rows"] for _, h in st)
    cfg = run["config"]
    return kernel_roofline(run, PATTERNS, work.flops(cfg, cw, cs),
                           work.nbytes(cfg, cw, cs, rows))
