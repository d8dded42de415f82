"""Model step: FLOPs the tokens of the window require of a decoder-hybrid-
decoder (benchmarks/roofline/hybrid_step.py: prompt positions without logits
pass the self-decoder only; attention by kind at the lengths attended; the
scan) over the window and the peak of the chips used. The by-kind context
sums are the program's ``serving_decode_ctx_window`` / ``_shared`` counters,
read per step by drivers/serve_hybrid.py (``run["hybrid_steps"]``)."""
from benchmarks.hybrid_readers import window_pairs
from benchmarks.roofline import hybrid_step


def read(run):
    st = window_pairs(run)
    if not st:
        return None
    first = [r["prompt_len"] for r in run["requests"]
             if r["first_token_t"] is not None
             and 0.0 <= r["first_token_t"] < run["seconds"]]
    flops = hybrid_step.serve_flops(
        run["config"], [c for s, _ in st for c in s["prefill_chunks"]], first,
        sum(h["decode_rows"] for _, h in st),
        sum(h["decode_ctx_window"] for _, h in st),
        sum(h["decode_ctx_shared"] for _, h in st))
    return 100.0 * flops / run["seconds"] / (
        run["chips"] * run["peaks"]["bf16_flops"])
