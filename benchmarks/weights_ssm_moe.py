"""Weight-shape table and seeded draws of the Mamba-2 + attention +
routed-experts family (Granite 4.0-H: a Mamba-2 or an attention mixer, a
router over all published experts, the HELD experts' stacked matrices, a
shared MLP of its own width, in every layer; a tied head), beside
``weights.py``'s table for dense decoders. Same rules: made on the device from
``--seed``, in the type the model is served in. ONE jitted program per LEAF
(leaves of one shape and kind share an executable), each of the program's own
initial parameters DELETED before its successor is drawn
(``weights_latent_moe.make_weights`` says why). Names and layouts are the
benchmark's own (``[in, out]`` matrices, ``x @ W``; expert stacks ``[held, in,
out]``); the driver maps them onto its program's parameter names.

Kinds of leaf: ``matrix`` normal(0, ``initializer_range``) — the router's
matrix too, and NO bias anywhere in the router: a seed must not change the
work (PERF.md section 6, PR 33); ``embed`` normal(0, ``initializer_range`` /
``embedding_multiplier``), so that the residual stream starts at the width
every other leaf is drawn for: drawn at ``initializer_range`` and multiplied
by 12, the tied head would put the INPUT token's logit eleven standard
deviations above every other (12 |e|^2 against |e|), the model would repeat
its input whatever the arithmetic, and the comparison of the served tokens
could catch nothing (PERF.md section 6, PR 35); ``ones`` (norm weights, ``D``); ``conv``
normal(0, 0.5) (a four-tap filter with unit output variance, as for Phi);
``conv_b`` normal(0, ``initializer_range``) (a bias that a program which
dropped it would be caught by); ``a_log`` the log of a uniform draw from [1,
16] and ``dt_bias`` the inverse softplus of a log-uniform draw from [1e-3,
1e-1], one value a head (both as the Mamba-2 reference implementation
initialises them: they decide how long a head remembers, so normal(0, 0.02)
would be no Mamba).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .reference.ssm_moe_lm import layer_kinds, sizes
from .weights import n_params, seed_key  # noqa: F401  (n_params: re-export)


def ssm_moe_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    z = sizes(cfg)
    H, F, Fs, di, N, mh = z["H"], z["F"], z["Fs"], z["di"], z["N"], z["mh"]
    nq, nkv = z["heads"] * z["d"], z["kv_heads"] * z["d"]
    held = z["held"][1] - z["held"][0]
    out = {"embed": ((z["V"], H), "embed"), "final_norm": ((H,), "ones")}
    for i, kind in enumerate(layer_kinds(cfg)):
        leaves = {"mix_norm": ((H,), "ones"), "mlp_norm": ((H,), "ones"),
                  "router": ((H, z["E"]), "matrix"),
                  "w_gate_e": ((held, H, F), "matrix"),
                  "w_up_e": ((held, H, F), "matrix"),
                  "w_down_e": ((held, F, H), "matrix"),
                  "ws_gate": ((H, Fs), "matrix"), "ws_up": ((H, Fs), "matrix"),
                  "ws_down": ((Fs, H), "matrix")}
        if kind == "mamba":
            leaves.update({
                "in_proj": ((H, 2 * di + 2 * N + mh), "matrix"),
                "conv_w": ((z["K"], di + 2 * N), "conv"),
                "conv_b": ((di + 2 * N,), "conv_b"),
                "dt_bias": ((mh,), "dt_bias"), "A_log": ((mh,), "a_log"),
                "D": ((mh,), "ones"), "ssm_norm": ((di,), "ones"),
                "out_proj": ((di, H), "matrix")})
        else:
            leaves.update({"wq": ((H, nq), "matrix"), "wk": ((H, nkv), "matrix"),
                           "wv": ((H, nkv), "matrix"),
                           "wo": ((nq, H), "matrix")})
        out.update({f"layers.{i}.{n}": v for n, v in leaves.items()})
    return out


def _draw(key, shape, kind, std, embed_std):
    f = jnp.float32
    if kind == "ones":
        return jnp.ones(shape, f)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, f, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f, math.log(1e-3),
                                        math.log(1e-1)))
        return jnp.log(jnp.expm1(dt))
    scale = {"matrix": std, "conv_b": std, "conv": 0.5,
             "embed": embed_std}[kind]
    return jax.random.normal(key, shape, f) * scale


def make_weights(shapes, seed: int, dtype, std: float = 0.02,
                 donate: Dict[str, jax.Array] = None,
                 embed_std: float = None) -> Dict[str, jax.Array]:
    """``embed_std`` (None: ``std``) is the width of the embedding;
    ``donate`` as in ``weights_latent_moe.make_weights``."""
    dtype = jnp.dtype(dtype)
    embed_std = std if embed_std is None else embed_std
    fn = jax.jit(lambda key, shape, kind: _draw(key, shape, kind, std,
                                                embed_std).astype(dtype),
                 static_argnums=(1, 2))
    key = seed_key(seed)
    out: Dict[str, jax.Array] = {}
    for i, n in enumerate(sorted(shapes)):
        if donate is not None:
            donate[n].delete()
        out[n] = fn(jax.random.fold_in(key, i), tuple(shapes[n][0]),
                    shapes[n][1])
    return out
