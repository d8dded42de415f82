"""Seeded weights, made ON THE DEVICE by one jitted program per decoder layer,
in the type they are served or trained in.

The benchmark makes the weights (not the program), so that the program under
test and the plain reference start from the same numbers without the
reference taking anything the program has made. Names and layouts are the
benchmark's own (``[in, out]`` matrices, ``x @ W``); a driver maps them onto
its program's parameter names.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp


def decoder_shapes(cfg: dict) -> Dict[str, Tuple[Tuple[int, ...], str]]:
    """name -> (shape, kind) of a Llama/Mistral-style decoder: RMSNorm,
    GQA attention with rotary embeddings, SwiGLU MLP, untied head, no
    biases. kind: "matrix" (normal(0, initializer_range)) or "norm" (ones)."""
    H, F, V = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    D = cfg.get("head_dim") or H // cfg["num_attention_heads"]
    nq, nkv = cfg["num_attention_heads"] * D, cfg["num_key_value_heads"] * D
    out = {"embed": ((V, H), "matrix")}
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        out.update({
            p + "attn_norm": ((H,), "norm"),
            p + "wq": ((H, nq), "matrix"), p + "wk": ((H, nkv), "matrix"),
            p + "wv": ((H, nkv), "matrix"), p + "wo": ((nq, H), "matrix"),
            p + "mlp_norm": ((H,), "norm"),
            p + "w_gate": ((H, F), "matrix"), p + "w_up": ((H, F), "matrix"),
            p + "w_down": ((F, H), "matrix")})
    out["final_norm"] = ((H,), "norm")
    if not cfg.get("tie_word_embeddings", False):
        out["lm_head"] = ((H, V), "matrix")
    return out


def seed_key(seed: int):
    """A PRNG key from any non-negative seed up to 2**63 (the driver's are
    larger than 32 signed bits hold)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def _group(name: str) -> str:
    """Leaves are made a group at a time: one decoder layer, or the leaves
    outside the layers."""
    return name.rsplit(".", 1)[0] if name.startswith("layers.") else ""


def make_weights(shapes: Dict[str, Tuple[Tuple[int, ...], str]], seed: int,
                 dtype, std: float = 0.02, donate: Dict[str, jax.Array] = None
                 ) -> Dict[str, jax.Array]:
    """One jitted program per GROUP of leaves (a decoder layer; the rest),
    so every layer reuses one executable and the float32 draws of at most
    one layer exist at a time (all leaves in one program peaked at 15 GB for
    7.5 GB of bf16: XLA drew every leaf before it cast any — my chip run,
    PR 23). ``donate``: arrays of the same shapes and dtype whose buffers the
    new weights take over (a program's own initial parameters), so the model
    is never held twice."""
    dtype = jnp.dtype(dtype)
    groups: Dict[str, list] = {}
    for n in sorted(shapes):
        groups.setdefault(_group(n), []).append(n)

    def build(key, spec, _old):
        out = []
        for i, (shape, kind) in enumerate(spec):
            if kind == "norm":
                out.append(jnp.ones(shape, dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((jax.random.normal(k, shape, jnp.float32)
                            * std).astype(dtype))
        return out

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


def n_params(shapes) -> int:
    n = 0
    for shape, _ in shapes.values():
        k = 1
        for s in shape:
            k *= s
        n += k
    return n
