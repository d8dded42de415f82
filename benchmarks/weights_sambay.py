"""Weight-shape table and seeded draws for the SambaY family (Phi-4-mini-flash:
Mamba, differential attention, gated memory units), beside ``weights.py``'s
table for dense decoders. Same rules: made on the device by one jitted
program per layer (layers of one kind share an executable), in the type the
model is served in; names and layouts are the benchmark's own (``[in, out]``
matrices, ``x @ W``; ``A_log`` is ``[d_state, d_inner]``), and the driver
maps them onto its program's parameter names.

Kinds of leaf: ``matrix`` normal(0, initializer_range); ``ones`` / ``zeros``
(norm weights and biases, ``D``, the convolution's bias); ``lambda``
normal(0, 0.1) (the differential heads' lambda vectors, as the Differential
Transformer initialises them); ``conv`` normal(0, 0.5) (a four-tap filter
with unit output variance); ``a_log`` log(1..d_state) down the state axis and
``dt_bias`` the inverse softplus of a log-uniform draw from [1e-3, 1e-1]
(both as the Mamba reference implementation initialises them: they decide
how long the state remembers, so normal(0, 0.02) would not be a Mamba).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from .reference.sambay_lm import layer_kinds, sizes
from .weights import _group, n_params, seed_key  # noqa: F401  (n_params: re-export)


def sambay_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    z = sizes(cfg)
    H, F, di, S, K, r, d = (z["H"], z["F"], z["di"], z["S"], z["K"], z["r"],
                            z["d"])
    nq, nkv = z["heads"] * d, z["kv_heads"] * d
    out = {"embed": ((z["V"], H), "matrix"),
           "final_norm_w": ((H,), "ones"), "final_norm_b": ((H,), "zeros")}
    lam = {n: ((d,), "lambda") for n in ("lambda_q1", "lambda_k1",
                                         "lambda_q2", "lambda_k2")}
    for i, kind in enumerate(layer_kinds(cfg)):
        leaves = {"norm1_w": ((H,), "ones"), "norm1_b": ((H,), "zeros"),
                  "norm2_w": ((H,), "ones"), "norm2_b": ((H,), "zeros"),
                  "w_gate": ((H, F), "matrix"), "w_up": ((H, F), "matrix"),
                  "w_down": ((F, H), "matrix")}
        if kind == "mamba":
            leaves.update({
                "in_proj": ((H, 2 * di), "matrix"),
                "conv_w": ((K, di), "conv"), "conv_b": ((di,), "zeros"),
                "x_proj": ((di, r + 2 * S), "matrix"),
                "dt_proj": ((r, di), "matrix"), "dt_bias": ((di,), "dt_bias"),
                "A_log": ((S, di), "a_log"), "D": ((di,), "ones"),
                "out_proj": ((di, H), "matrix")})
        elif kind == "gmu":
            leaves.update({"w1": ((H, di), "matrix"),
                           "w2": ((di, H), "matrix")})
        else:
            leaves.update({"wq": ((H, nq), "matrix"), "wo": ((nq, H), "matrix"),
                           "subln": ((2 * d,), "ones"), **lam})
            if kind != "cross":
                leaves.update({"wk": ((H, nkv), "matrix"),
                               "wv": ((H, nkv), "matrix")})
        out.update({f"layers.{i}.{n}": v for n, v in leaves.items()})
    return out


def _draw(key, shape, kind, std):
    f = jnp.float32
    if kind == "ones":
        return jnp.ones(shape, f)
    if kind == "zeros":
        return jnp.zeros(shape, f)
    if kind == "a_log":
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=f))[:, None], shape)
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, f, math.log(1e-3),
                                        math.log(1e-1)))
        return jnp.log(jnp.expm1(dt))
    scale = {"matrix": std, "lambda": 0.1, "conv": 0.5}[kind]
    return jax.random.normal(key, shape, f) * scale


def make_weights(shapes, seed: int, dtype, std: float = 0.02,
                 donate: Dict[str, jax.Array] = None) -> Dict[str, jax.Array]:
    """``weights.make_weights`` with this family's kinds of leaf (that
    function draws every leaf that is not a norm as normal(0, std), and is
    not this PR's to edit): one jitted program per group of leaves (a layer;
    the rest), the float32 draws of one group alive at a time; ``donate`` as
    there."""
    dtype = jnp.dtype(dtype)
    groups: Dict[str, list] = {}
    for n in sorted(shapes):
        groups.setdefault(_group(n), []).append(n)

    def build(key, spec, _old):
        return [_draw(jax.random.fold_in(key, i), shape, kind, std).astype(
            dtype) for i, (shape, kind) in enumerate(spec)]

    fn = jax.jit(build, static_argnums=(1,),
                 donate_argnums=(2,) if donate else ())
    key = seed_key(seed)
    out: Dict[str, jax.Array] = {}
    for gi, (_, names) in enumerate(sorted(groups.items())):
        spec = tuple((tuple(shapes[n][0]), shapes[n][1]) for n in names)
        old = [donate[n] for n in names] if donate else None
        for n, w in zip(names, fn(jax.random.fold_in(key, gi), spec, old)):
            out[n] = w
    return out
