"""Decode attention over a paged KV cache: one query token per row against
the ``ctx`` cached positions it attends (its own included)."""


def flops(n_heads: int, head_dim: int, ctx_sum: int, layers: int) -> float:
    """Q·Kᵀ and P·V: 2·D multiply-adds each per (head, attended position)."""
    return 4.0 * n_heads * head_dim * ctx_sum * layers


def nbytes(n_heads: int, n_kv: int, head_dim: int, ctx_sum: int, rows: int,
           layers: int, kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """K and V of every attended position read ONCE (shared by the query
    heads of a group), the query read and the output written."""
    kv = 2.0 * n_kv * head_dim * ctx_sum * kv_bytes
    qo = 2.0 * n_heads * head_dim * rows * act_bytes
    return (kv + qo) * layers
