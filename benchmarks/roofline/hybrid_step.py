"""Model FLOPs that a window's tokens REQUIRE of a decoder-hybrid-decoder
(SambaY: Phi-4-mini-flash; for ``mfu.hybrid_decode``), beside
``decoder_step.py``'s count for a dense decoder.

Per token, 2 FLOPs per parameter of every layer the token MUST pass: a
prompt position whose logits nobody needs passes the self-decoder only
(layers ``0 .. L/2 + 1``: nothing above keeps state, the published design);
a position whose logits are needed (a prompt's last token, every decoded
token) passes all ``L`` layers and the head. Attention at the lengths
attended, by kind: a window layer at ``min(context, window)``, the full layer
and the cross layers at the whole context. The scan: per token and Mamba
layer ``2 * 3 * d_inner * d_state`` (the decay product, the input product
and the read-out, a multiply-add each per state element). Recomputation is
not counted; the embedding lookup has no FLOPs.
"""
from ..reference.sambay_lm import layer_kinds, sizes


def layer_params(cfg: dict) -> dict:
    """Matrix parameters of ONE layer of each kind (MLP included)."""
    z = sizes(cfg)
    H, F, di, S, K, r, d = (z["H"], z["F"], z["di"], z["S"], z["K"], z["r"],
                            z["d"])
    nq, nkv = z["heads"] * d, z["kv_heads"] * d
    mlp = 3 * H * F
    return {"mamba": mlp + H * 2 * di + K * di + di * (r + 2 * S) + r * di
            + di * H,
            "window": mlp + 2 * H * nq + 2 * H * nkv,
            "full": mlp + 2 * H * nq + 2 * H * nkv,
            "cross": mlp + 2 * H * nq,
            "gmu": mlp + 2 * H * di}


def params_passed(cfg: dict) -> tuple:
    """(parameters a stateless-above prompt position passes, parameters a
    position with logits passes), the head apart."""
    per = layer_params(cfg)
    kinds = layer_kinds(cfg)
    lower = kinds[:cfg["num_hidden_layers"] // 2 + 2]
    return sum(per[k] for k in lower), sum(per[k] for k in kinds)


def window_attended(start: int, n: int, window: int) -> int:
    """Sum over a chunk's queries (positions start .. start+n-1) of the
    positions each attends in a window layer."""
    return sum(min(p + 1, window) for p in range(start, start + n))


def serve_flops(cfg: dict, prefill_chunks, first_token_ctx, decode_rows: int,
                ctx_window: int, ctx_shared: int) -> float:
    """``prefill_chunks``: (start, n) of every chunk run; ``first_token_ctx``:
    the prompt length of every request whose first token came out (its last
    prompt position went through all layers); ``decode_rows`` decoded
    tokens, which attended ``ctx_window`` positions in a window layer and
    ``ctx_shared`` in the full layer, summed."""
    from .prefill_attention import attended

    z = sizes(cfg)
    kinds = layer_kinds(cfg)
    n = {k: kinds.count(k) for k in set(kinds)}
    lower, whole = params_passed(cfg)
    prompt = sum(c for _, c in prefill_chunks)
    logits_rows = decode_rows + len(first_token_ctx)
    dense = 2.0 * (lower * prompt + (whole - lower) * len(first_token_ctx)
                   + whole * decode_rows)
    head = 2.0 * z["H"] * z["V"] * logits_rows
    per_pos = 4.0 * z["heads"] * z["d"]        # QK^T and PV, per position
    attn = per_pos * (
        n["window"] * (ctx_window + sum(window_attended(s, c, z["window"])
                                        for s, c in prefill_chunks))
        + n["full"] * (ctx_shared + sum(attended(s, c)
                                        for s, c in prefill_chunks))
        + n["cross"] * (ctx_shared + sum(first_token_ctx)))
    scan = 2.0 * 3 * z["di"] * z["S"] * n["mamba"] * (prompt + decode_rows)
    return dense + head + attn + scan
