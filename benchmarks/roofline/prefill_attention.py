"""Causal attention of one prefill chunk: ``n`` query tokens at positions
``start .. start+n-1`` against everything cached before them and themselves."""


def attended(start: int, n: int) -> int:
    """Σ over the chunk's queries of the positions each attends."""
    return n * start + n * (n + 1) // 2


def flops(n_heads: int, head_dim: int, chunks, layers: int) -> float:
    return 4.0 * n_heads * head_dim * layers * sum(
        attended(s, n) for s, n in chunks)


def nbytes(n_heads: int, n_kv: int, head_dim: int, chunks, layers: int,
           kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """K/V of the context read once per chunk, Q read and O written."""
    kv = sum(2.0 * n_kv * head_dim * (s + n) * kv_bytes for s, n in chunks)
    qo = sum(2.0 * n_heads * head_dim * n * act_bytes for s, n in chunks)
    return (kv + qo) * layers
