"""Weight-shape table of the window + full attention, dense-then-routed-experts
family (Trinity's ``afmoe``: gated, QK-normed attention; SwiGLU in the first
``num_dense_layers`` layers, then a router over all published experts, the
HELD experts' stacked matrices and a shared expert; an untied head), beside
``weights.py``'s table for dense decoders. The draws are
``weights_latent_moe.make_weights``'s, imported: made on the device from
``--seed``, in the type the model is served in, one jitted program per leaf —
every matrix normal(0, ``initializer_range``), norms at 1, the router's
selection bias normal(0, ``router_bias_range``): small, so that it breaks
ties and is no prior that lets a seed choose its own popular experts (PERF.md
section 6). The embedding is a matrix like the others: the head is
untied, so the input token's own logit is no larger than any other's. Names
and layouts are the benchmark's own (``[in, out]`` matrices, ``x @ W``;
expert stacks ``[held, in, out]``); the driver maps them onto its program's
parameter names.
"""
from __future__ import annotations

from typing import Dict, Tuple

from .reference.window_moe_lm import sizes
from .weights import n_params  # noqa: F401  (re-export)
from .weights_latent_moe import make_weights  # noqa: F401  (re-export)


def window_moe_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    z = sizes(cfg)
    H, d = z["H"], z["d"]
    nq, nkv = z["heads"] * d, z["kv_heads"] * d
    held = z["held"][1] - z["held"][0]
    out = {"embed": ((z["V"], H), "matrix"), "final_norm": ((H,), "norm"),
           "lm_head": ((H, z["V"]), "matrix")}
    for i in range(z["L"]):
        leaves = {"attn_norm": ((H,), "norm"), "wq": ((H, nq), "matrix"),
                  "wk": ((H, nkv), "matrix"), "wv": ((H, nkv), "matrix"),
                  "wg": ((H, nq), "matrix"), "wo": ((nq, H), "matrix"),
                  "q_norm": ((d,), "norm"), "k_norm": ((d,), "norm"),
                  "post_attn_norm": ((H,), "norm"),
                  "pre_mlp_norm": ((H,), "norm"),
                  "post_mlp_norm": ((H,), "norm")}
        if i < z["dense"]:
            leaves.update({"w_gate": ((H, z["Fd"]), "matrix"),
                           "w_up": ((H, z["Fd"]), "matrix"),
                           "w_down": ((z["Fd"], H), "matrix")})
        else:
            F, Fs = z["F"], z["Fs"]
            leaves.update({"router": ((H, z["E"]), "matrix"),
                           "router_bias": ((z["E"],), "bias"),
                           "w_gate_e": ((held, H, F), "matrix"),
                           "w_up_e": ((held, H, F), "matrix"),
                           "w_down_e": ((held, F, H), "matrix"),
                           "ws_gate": ((H, Fs), "matrix"),
                           "ws_up": ((H, Fs), "matrix"),
                           "ws_down": ((Fs, H), "matrix")})
        out.update({f"layers.{i}.{n}": v for n, v in leaves.items()})
    return out
