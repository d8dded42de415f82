"""Model step: FLOPs the tokens of the window require of one chip's share of
a window + full attention, dense-then-experts decoder (benchmarks/roofline/
window_moe_step.py: the rows' own attention, dense MLP, shared expert and
router; the routed experts by the pairs HELD here, from the program's
``serving_moe_pairs{held="1"}``; attention at the positions attended by
kind, from the step records and the program's ``serving_decode_ctx_window``
as drivers/serve_window_moe.py keeps them per step; the head per emitted
token) over the window and the peak of the chips used."""
from benchmarks import window_moe_readers as wr
from benchmarks.latent_moe_readers import total, window_pairs
from benchmarks.roofline import window_moe_step


def read(run):
    st = window_pairs(run)
    held = total(st, "pairs_held")
    attn = wr.window_pairs(run)
    pos = wr.positions(run, attn, "window") + wr.positions(run, attn, "full")
    if not st or not held or not pos:
        return None
    tokens = sum(s["decode_rows"] + sum(n for _, n in s["prefill_chunks"])
                 for s, _ in st)
    flops = window_moe_step.serve_flops(
        run["config"], tokens, pos, sum(s["tokens"] for s, _ in st), held)
    return 100.0 * flops / run["seconds"] / (
        run["chips"] * run["peaks"]["bf16_flops"])
