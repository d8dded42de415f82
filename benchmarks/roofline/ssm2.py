"""Mamba-2's one-step state update (decode): per decoded token and Mamba-2
layer, the float32 state ``[heads, head_dim, d_state]`` read and written
once, its operands (x in, y out, ``[d_inner]`` each; dt ``[heads]``; B, C
``[d_state]`` each) and, once per call and layer, A and D ``[heads]``. FLOPs:
decay, input and read-out, a multiply-add each per state element (the one
exponential a head is not counted)."""
from ..reference.ssm_moe_lm import layer_kinds, sizes


def step_flops(cfg: dict, rows: int) -> float:
    z = sizes(cfg)
    return 6.0 * z["di"] * z["N"] * rows * layer_kinds(cfg).count("mamba")


def step_nbytes(cfg: dict, rows: int, calls: int) -> float:
    """``rows`` decoded tokens over ``calls`` decode ticks."""
    z = sizes(cfg)
    per_row = 4.0 * (2 * z["di"] * z["N"] + 2 * z["di"] + z["mh"]
                     + 2 * z["N"])
    per_call = 4.0 * 2 * z["mh"]
    return (per_row * rows + per_call * calls) \
        * layer_kinds(cfg).count("mamba")
