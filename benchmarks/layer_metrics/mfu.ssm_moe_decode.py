"""Model step: FLOPs the tokens of the window require of one chip's share of
a Mamba-2 + attention + many-expert decoder (benchmarks/roofline/
ssm_moe_step.py: the rows' own mixers, shared MLP and router; the routed
experts by the pairs HELD here, from the program's ``serving_moe_pairs{held=
"1"}`` as drivers/serve_ssm_moe.py keeps it per step; the scan; attention at
the contexts seen; the head per emitted token) over the window and the peak
of the chips used."""
from benchmarks.latent_moe_readers import total, window_pairs
from benchmarks.roofline import ssm_moe_step


def read(run):
    st = window_pairs(run)
    held = total(st, "pairs_held")
    if not st or not held:
        return None
    flops = ssm_moe_step.serve_flops(
        run["config"], [c for s, _ in st for c in s["prefill_chunks"]],
        sum(s["decode_rows"] for s, _ in st),
        sum(s["decode_ctx"] for s, _ in st),
        sum(s["tokens"] for s, _ in st), held)
    return 100.0 * flops / run["seconds"] / (
        run["chips"] * run["peaks"]["bf16_flops"])
