"""Model FLOPs that a window's tokens REQUIRE of ONE CHIP'S SHARE of a
decoder with latent attention and a many-expert layer (for
``mfu.latent_moe_decode``), beside ``decoder_step.py``'s count for a dense
decoder.

Per token and layer, 2 FLOPs per parameter of what every chip computes for
its own rows — the query and latent projections, the up-projection of keys
and values (absorbed or not, the same count), the output projection, the
router at its published width, the shared expert — and ``6 * hidden * width``
per (token, expert) pair whose expert is HELD here (the program's
``serving_moe_pairs{held="1"}``): what the absent experts would have cost is
their chips' work and is not counted. Attention in the absorbed form at the
contexts attended (``latent_attention.py``); the output head over the held
slice of the vocabulary for every position whose logits are needed.
Recomputation is not counted; the embedding lookup has no FLOPs."""
from ..reference.latent_moe_lm import sizes
from . import expert_matmul, latent_attention
from .prefill_attention import attended


def row_params(cfg: dict) -> int:
    """Matrix parameters a token passes in ONE layer outside the routed
    experts."""
    z = sizes(cfg)
    H, nh = z["H"], z["heads"]
    return (H * z["rq"] + z["rq"] * nh * (z["dn"] + z["dr"])
            + H * (z["rkv"] + z["dr"]) + z["rkv"] * nh * (z["dn"] + z["dv"])
            + nh * z["dv"] * H + H * z["E"] + 3 * H * z["F"] * z["n_shared"])


def serve_flops(cfg: dict, prefill_chunks, decode_rows: int, decode_ctx: int,
                logits_rows: int, pairs_held: int) -> float:
    z = sizes(cfg)
    tokens = decode_rows + sum(n for _, n in prefill_chunks)
    ctx = decode_ctx + sum(attended(s, n) for s, n in prefill_chunks)
    return (2.0 * row_params(cfg) * z["L"] * tokens
            + expert_matmul.flops(cfg, pairs_held)
            + latent_attention.flops(cfg, ctx)
            + 2.0 * z["H"] * z["V"] * logits_rows)
