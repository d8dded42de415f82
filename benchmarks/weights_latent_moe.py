"""Weight-shape table of the latent-attention + routed-experts family
(Mistral-Small-4's language model: MLA, a router over all published experts,
the HELD experts' stacked matrices, a shared expert), beside ``weights.py``'s
table for dense decoders. Same rules: made on the device from ``--seed``, in
the type the model is served in: every matrix normal(0,
``initializer_range``), norms at 1, and the router's per-expert selection
bias normal(0, ``bias_std``), so that selection and weights differ as in a
trained model. ``bias_std`` is the configuration's ``router_bias_range``:
sigmoid scores are flat at the top (the 4 chosen of 128 lie 0.015 apart),
so a bias as wide as the matrices (0.02) is no tie-breaker but a prior —
each seed then drew its own popular experts (per-expert load uneven by
0.32-0.53 of its mean, 180.8 or 183.0 of 192 held experts active a tick,
ticks 2.6 % apart between two seeds' weights: my chip runs, PR 33), which a
trained model's bias exists to prevent. ONE jitted program per LEAF (leaves of one
shape share an executable): ``weights.make_weights`` draws a whole layer in
one program, and a layer of this family is 859 M values, 3.4 GB of float32
draws; a leaf's largest draw is one expert stack, 1.07 GB. Names and layouts
are the benchmark's own (``[in, out]`` matrices, ``x @ W``; expert stacks
``[held, in, out]``); the driver maps them onto its program's parameter
names.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .reference.latent_moe_lm import sizes
from .weights import n_params, seed_key  # noqa: F401  (n_params: re-export)


def latent_moe_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    z = sizes(cfg)
    H, F, nh = z["H"], z["F"], z["heads"]
    held = z["held"][1] - z["held"][0]
    out = {"embed": ((z["V"], H), "matrix"), "final_norm": ((H,), "norm"),
           "lm_head": ((H, z["V"]), "matrix")}
    for i in range(z["L"]):
        p = f"layers.{i}."
        out.update({
            p + "attn_norm": ((H,), "norm"),
            p + "w_dq": ((H, z["rq"]), "matrix"),
            p + "q_norm": ((z["rq"],), "norm"),
            p + "w_uq": ((z["rq"], nh * (z["dn"] + z["dr"])), "matrix"),
            p + "w_dkv": ((H, z["rkv"] + z["dr"]), "matrix"),
            p + "kv_norm": ((z["rkv"],), "norm"),
            p + "w_ukv": ((z["rkv"], nh * (z["dn"] + z["dv"])), "matrix"),
            p + "wo": ((nh * z["dv"], H), "matrix"),
            p + "mlp_norm": ((H,), "norm"),
            p + "router": ((H, z["E"]), "matrix"),
            p + "router_bias": ((z["E"],), "bias"),
            p + "w_gate_e": ((held, H, F), "matrix"),
            p + "w_up_e": ((held, H, F), "matrix"),
            p + "w_down_e": ((held, F, H), "matrix"),
            p + "ws_gate": ((H, z["n_shared"] * F), "matrix"),
            p + "ws_up": ((H, z["n_shared"] * F), "matrix"),
            p + "ws_down": ((z["n_shared"] * F, H), "matrix")})
    return out


def make_weights(shapes, seed: int, dtype, std: float = 0.02,
                 donate: Dict[str, jax.Array] = None,
                 bias_std: float = None) -> Dict[str, jax.Array]:
    """``bias_std`` (None: ``std``) is the width of the leaves of kind
    "bias". ``donate``: arrays of the same shapes (the program's own initial
    parameters) that the new weights take the place of: each is DELETED
    before its successor is drawn, so the model is never held twice. (Handing
    them to jit as donated operands frees nothing: an operand the program
    does not read is dropped from it, and its buffer lives on with the
    parameter that holds it — 10.85 GB of old beside the new ran the chip
    out at the third layer; my chip runs, PR 33.)"""
    dtype = jnp.dtype(dtype)
    width = {"matrix": std, "bias": std if bias_std is None else bias_std}

    def build(key, shape, kind):
        if kind == "norm":
            return jnp.ones(shape, dtype)
        return (jax.random.normal(key, shape, jnp.float32)
                * width[kind]).astype(dtype)

    fn = jax.jit(build, static_argnums=(1, 2))
    key = seed_key(seed)
    out: Dict[str, jax.Array] = {}
    for i, n in enumerate(sorted(shapes)):
        if donate is not None:
            donate[n].delete()
        out[n] = fn(jax.random.fold_in(key, i), tuple(shapes[n][0]),
                    shapes[n][1])
    return out
