"""Attention over a latent (MLA) cache in the absorbed form: every query
head against ONE cached row a position — the normed latent (``kv_lora_rank``
values, which is also the value) and the one rotated rope key
(``qk_rope_head_dim``). Per attended position and layer: scores over the
whole row and the probabilities' sum over the latent part, a multiply-add
each per head and value; the row read once, shared by all heads. The same
work whatever implements it (an expanded form would compute more)."""
from ..reference.latent_moe_lm import sizes


def flops(cfg: dict, ctx_sum: int) -> float:
    """``ctx_sum``: positions attended, summed over query tokens (per ONE
    layer)."""
    z = sizes(cfg)
    return 2.0 * z["heads"] * (2 * z["rkv"] + z["dr"]) * ctx_sum * z["L"]


def nbytes(cfg: dict, ctx_sum: int, kv_bytes: int = 2) -> float:
    z = sizes(cfg)
    return float(z["rkv"] + z["dr"]) * kv_bytes * ctx_sum * z["L"]
