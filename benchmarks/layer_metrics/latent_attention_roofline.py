"""Kernels: attention over the paged latent cache — the decode rows' and the
prompt chunks' calls alike — share of its roofline. Work = what the absorbed
form needs for the positions attended in the traced steps
(benchmarks/roofline/latent_attention.py: 2 * 32 * (320 + 256) FLOPs over 640
bytes a position a layer at the published widths), whatever implements it;
time = device time of the matching trace events."""
from benchmarks.readers import kernel_roofline, traced_steps
from benchmarks.roofline import latent_attention as work
from benchmarks.roofline.prefill_attention import attended

# By its name where the trace carries one; else by structure (an op's trace
# event on the v5e is its whole HLO text and a Pallas kernel a nameless
# custom-call): the custom-call whose output is [B, rows, value width] and
# whose first operands are the s32 [B, M] block tables and the s32 [B]
# positions. (The K/V-pool attention kernels return 4-D outputs.)
PATTERNS = [r"latent_attend",
            r"= \w+\[(\d+),\d+,\d+\]\S* custom-call\(s32\[\1,\d+\]"
            r"\S* %\S+ s32\[\1\]"]


def read(run):
    st = traced_steps(run)
    if not st or "kv_lora_rank" not in run["config"]:
        return None
    ctx = sum(s["decode_ctx"] for s in st) + sum(
        attended(a, n) for s in st for a, n in s["prefill_chunks"])
    cfg = run["config"]
    return kernel_roofline(run, PATTERNS, work.flops(cfg, ctx),
                           work.nbytes(cfg, ctx))
