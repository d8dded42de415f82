"""The selective scan's one-step state update (decode): per decoded token and
Mamba layer, the float32 state ``[d_state, d_inner]`` read and written once,
its operands (x, dt in, y out, ``[d_inner]`` each; B, C ``[d_state]`` each)
and, once per call and layer, A ``[d_state, d_inner]`` and D. FLOPs: decay,
input and read-out, a multiply-add each per state element (the exponential
counted as one more operation)."""
from ..reference.sambay_lm import layer_kinds, sizes


def step_flops(cfg: dict, rows: int) -> float:
    z = sizes(cfg)
    return 7.0 * z["di"] * z["S"] * rows * layer_kinds(cfg).count("mamba")


def step_nbytes(cfg: dict, rows: int, calls: int) -> float:
    """``rows`` decoded tokens over ``calls`` decode ticks."""
    z = sizes(cfg)
    per_row = 4.0 * (2 * z["S"] * z["di"] + 3 * z["di"] + 2 * z["S"])
    per_call = 4.0 * (z["S"] * z["di"] + z["di"])
    return (per_row * rows + per_call * calls) \
        * layer_kinds(cfg).count("mamba")
