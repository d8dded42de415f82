"""Model FLOPs that a window's tokens REQUIRE of ONE CHIP'S SHARE of a
decoder of window and full attention layers with dense layers first and
many experts after (Trinity; for ``mfu.window_moe_decode``), beside
``decoder_step.py``'s count for a dense decoder.

Per token and layer, 2 FLOPs per parameter of what every chip computes for
its own rows — the attention's five matrices (q, k, v, the gate and o) and
its q/k norms' scaling, and then in a dense layer the SwiGLU of
``intermediate_size``, in an expert layer the router at its published width
and the shared expert — and ``6 * hidden * width`` per (token, expert) pair
whose expert is HELD here (the program's ``serving_moe_pairs{held="1"}``):
what the absent experts would have cost is their chips' work and is not
counted. Attention at the positions attended, by kind
(``window_moe_attention.flops``). The output head
over the held slice of the vocabulary for every position whose logits are
needed. Recomputation is not counted; the embedding lookup has no FLOPs."""
from ..reference.window_moe_lm import sizes
from . import window_moe_attention, window_moe_experts


def row_params(cfg: dict) -> dict:
    """Matrix parameters a token passes in ONE layer outside the routed
    experts, by the layer's FFN."""
    z = sizes(cfg)
    H = z["H"]
    nq, nkv = z["heads"] * z["d"], z["kv_heads"] * z["d"]
    attn = 3 * H * nq + 2 * H * nkv
    return {"dense": attn + 3 * H * z["Fd"],
            "experts": attn + H * z["E"] + 3 * H * z["Fs"]}


def layer_params(cfg: dict) -> int:
    z = sizes(cfg)
    per = row_params(cfg)
    return z["dense"] * per["dense"] + (z["L"] - z["dense"]) * per["experts"]


def serve_flops(cfg: dict, tokens: int, positions: int, logits_rows: int,
                pairs_held: int) -> float:
    """``tokens``: decode rows and prompt tokens run; ``positions``: the
    positions attended, summed over layers and kinds."""
    z = sizes(cfg)
    return (2.0 * layer_params(cfg) * tokens
            + window_moe_experts.flops(cfg, pairs_held)
            + window_moe_attention.flops(cfg, positions)
            + 2.0 * z["H"] * z["V"] * logits_rows)
