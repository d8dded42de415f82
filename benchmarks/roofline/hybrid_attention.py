"""Decode attention of a model whose layers keep different kinds of cache
(SambaY: Phi-4-mini-flash): one query token per row against the positions it
attends, by kind. ``ctx_window``: positions attended in ONE window layer
(each row's context capped at the window), summed over the rows;
``ctx_shared``: the same in the one full layer, whose K and V the cross
layers read again — each of those layers runs between two MLPs, so each has
to read them for itself."""
from ..reference.sambay_lm import layer_kinds, sizes


def layers_by_kind(cfg: dict) -> dict:
    kinds = layer_kinds(cfg)
    return {"window": kinds.count("window"),
            "shared": kinds.count("full") + kinds.count("cross")}


def flops(cfg: dict, ctx_window: int, ctx_shared: int) -> float:
    """QK^T for both halves of the pair and (P1 - lambda P2) V: 4 * heads *
    head_dim multiply-adds' worth per attended position and layer."""
    z, n = sizes(cfg), layers_by_kind(cfg)
    return 4.0 * z["heads"] * z["d"] * (n["window"] * ctx_window
                                        + n["shared"] * ctx_shared)


def nbytes(cfg: dict, ctx_window: int, ctx_shared: int, rows: int,
           kv_bytes: int = 2, act_bytes: int = 2) -> float:
    """K and V of every attended position read once per layer, the query
    read and the output written."""
    z, n = sizes(cfg), layers_by_kind(cfg)
    kv = 2.0 * z["kv_heads"] * z["d"] * kv_bytes
    qo = 2.0 * z["heads"] * z["d"] * rows * act_bytes
    return (n["window"] * (kv * ctx_window + qo)
            + n["shared"] * (kv * ctx_shared + qo))
