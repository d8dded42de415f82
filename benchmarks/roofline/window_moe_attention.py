"""Attention of a decoder whose layers are window and full attention mixed
(Trinity), by kind: every query row against the positions it attends
(summed over the layers of the kind, decode rows and prompt tokens alike;
``window_moe_readers.positions``). Per position
``4 * heads * head_dim`` FLOPs (scores and the weighted sum). Bytes: a
decode row reads the K and V of every position it attends (its own
context: nothing is shared between rows); a prompt chunk reads the K and V
of its context ONCE for all its queries — for a window layer the
``sliding_window`` positions before it at most, for a full layer all of
them — and its own; every query row reads its q and writes its o."""
from ..reference.window_moe_lm import layer_kinds, sizes
from .prefill_attention import attended


def layers_of(cfg: dict, kind: str) -> int:
    return layer_kinds(cfg).count(kind)


def chunk_positions(cfg: dict, kind: str, chunks) -> int:
    """Positions the prompt chunks ``(start, n)`` attend in ONE layer of
    ``kind``."""
    W = sizes(cfg)["W"]
    if kind == "full":
        return sum(attended(s, n) for s, n in chunks)
    return sum(min(p, W) for s, n in chunks for p in range(s + 1, s + n + 1))


def flops(cfg: dict, positions: int) -> float:
    z = sizes(cfg)
    return 4.0 * z["heads"] * z["d"] * positions


def nbytes(cfg: dict, kind: str, positions: int, decode_rows: int, chunks,
           kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """``positions``: summed over the layers of ``kind``, decode rows and
    ``chunks`` together; ``decode_rows`` and ``chunks`` per ONE layer."""
    z = sizes(cfg)
    n = layers_of(cfg, kind)
    kv = 2.0 * z["kv_heads"] * z["d"] * kv_bytes
    decode_pos = positions - n * chunk_positions(cfg, kind, chunks)
    ctx = sum((min(s, z["W"]) if kind == "window" else s) + m
              for s, m in chunks)
    rows = decode_rows + sum(m for _, m in chunks)
    return (kv * (decode_pos + n * ctx)
            + 2.0 * z["heads"] * z["d"] * act_bytes * n * rows)
