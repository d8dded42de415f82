"""Model FLOPs that a window's tokens REQUIRE of ONE CHIP'S SHARE of a
decoder of Mamba-2 layers, an attention layer every so often and a
many-expert block in every layer (for ``mfu.ssm_moe_decode``), beside
``decoder_step.py``'s count for a dense decoder.

Per token and layer, 2 FLOPs per parameter of what every chip computes for
its own rows — the mixer's matrices (Mamba-2: the input and output
projections and the convolution's taps; attention: q, k, v, o), the router
at its published width, the shared MLP — and ``6 * hidden * width`` per
(token, expert) pair whose expert is HELD here (the program's
``serving_moe_pairs{held="1"}``): what the absent experts would have cost is
their chips' work and is not counted. The scan: ``6 * d_inner * d_state`` per
token and Mamba-2 layer (decay, input and read-out, a multiply-add each per
state element — the sequential recurrence's count; what the chunked form adds
within a block is not required work). Attention at the contexts attended:
``4 * heads * head_dim`` per position. The output head over the held slice of
the vocabulary for every position whose logits are needed. Recomputation is
not counted; the embedding lookup has no FLOPs."""
from ..reference.ssm_moe_lm import layer_kinds, sizes
from . import ssm_moe_experts
from .prefill_attention import attended


def row_params(cfg: dict) -> dict:
    """Matrix parameters a token passes in ONE layer outside the routed
    experts, by the layer's kind."""
    z = sizes(cfg)
    H, di, N = z["H"], z["di"], z["N"]
    common = H * z["E"] + 3 * H * z["Fs"]
    nq, nkv = z["heads"] * z["d"], z["kv_heads"] * z["d"]
    return {"mamba": common + H * (2 * di + 2 * N + z["mh"]) + di * H
            + z["K"] * (di + 2 * N),
            "attention": common + 2 * H * nq + 2 * H * nkv}


def serve_flops(cfg: dict, prefill_chunks, decode_rows: int, decode_ctx: int,
                logits_rows: int, pairs_held: int) -> float:
    z = sizes(cfg)
    kinds = layer_kinds(cfg)
    per = row_params(cfg)
    tokens = decode_rows + sum(n for _, n in prefill_chunks)
    ctx = decode_ctx + sum(attended(s, n) for s, n in prefill_chunks)
    return (2.0 * sum(per[k] for k in kinds) * tokens
            + ssm_moe_experts.flops(cfg, pairs_held)
            + 6.0 * z["di"] * z["N"] * kinds.count("mamba") * tokens
            + 4.0 * z["heads"] * z["d"] * kinds.count("attention") * ctx
            + 2.0 * z["H"] * z["V"] * logits_rows)
