"""The grouped products of the HELD experts of a Mamba-2 + routed-experts
decoder's expert block, at that family's sizes (``expert_matmul.py`` counts
the same work and reads another family's configuration): per (token, expert)
pair whose expert lives here, three matrix products of a SwiGLU —
``6 * hidden * width`` FLOPs; per held expert that got at least one row in a
call, its three matrices read once; per pair its input row read and its
output row written. Experts that got no row cost nothing."""
from ..reference.ssm_moe_lm import sizes


def flops(cfg: dict, pairs_held: int) -> float:
    z = sizes(cfg)
    return 6.0 * z["H"] * z["F"] * pairs_held


def nbytes(cfg: dict, pairs_held: int, experts_active: int,
           w_bytes: int = 2, act_bytes: int = 2) -> float:
    """``experts_active``: held experts with at least one row, summed over
    layers and calls."""
    z = sizes(cfg)
    return (3.0 * z["H"] * z["F"] * w_bytes * experts_active
            + 2.0 * z["H"] * act_bytes * pairs_held)
