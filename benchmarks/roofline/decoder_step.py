"""Model FLOPs that a window's tokens REQUIRE of a dense decoder (for
``mfu.*``): 2 FLOPs per layer parameter per token through the layers, the
output head for every position whose logits are needed, and causal attention
at the lengths actually seen. Recomputation is not counted; the embedding
lookup has no FLOPs."""
from . import paged_attention, prefill_attention


def layer_params(cfg: dict) -> int:
    H, F = cfg["hidden_size"], cfg["intermediate_size"]
    D = cfg.get("head_dim") or H // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    return cfg["num_hidden_layers"] * (2 * H * nq + 2 * H * nkv + 3 * H * F)


def serve_flops(cfg: dict, prefill_chunks, decode_rows: int, decode_ctx: int,
                logits_rows: int) -> float:
    D = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    L, nh = cfg["num_hidden_layers"], cfg["num_attention_heads"]
    tokens = decode_rows + sum(n for _, n in prefill_chunks)
    dense = 2.0 * layer_params(cfg) * tokens
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * logits_rows
    attn = (paged_attention.flops(nh, D, decode_ctx, L)
            + prefill_attention.flops(nh, D, prefill_chunks, L))
    return dense + head + attn
