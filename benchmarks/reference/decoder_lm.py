"""Plain reference of a Llama/Mistral-style decoder language model.

Straightforward ``jax.numpy`` in float32 with "highest" matmul precision: no
kernels, no cache, no batching, nothing imported from ``paddle_tpu``. It
follows the published description (RMSNorm before attention and before the
MLP; grouped-query attention with rotary embeddings applied to the two
HALVES of each head, as the Hugging Face implementation of Mistral does;
causal softmax in float32; SwiGLU MLP; final RMSNorm; untied output head; no
biases; no sliding window where the config says ``null``).

It runs one sequence at a time, layer by layer (one small jitted function per
layer kind, compiled once per padded length), upcasting one layer's weights
at a time, so that it fits beside the model's bf16 weights.

``matmul`` selects the arithmetic, for the CONTROL of the correctness check:
``"f32"`` is the reference; ``"bf16"``, ``"int8"`` and ``"fp8"`` compute every
matrix product in that lower precision (inputs and weights rounded, float32
accumulation), everything else as the reference.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block (memory, not maths)


def _fake_int8(x, axis):
    """Symmetric absmax int8 quantize-dequantize along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s).clip(-127, 127) * s


def _mm(x, w, mode: str):
    """x [T, in] @ w [in, out] in the arithmetic ``mode`` names."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "bf16":
        x, w = x.astype(jnp.bfloat16), w.astype(jnp.bfloat16)
        return jnp.matmul(x, w, preferred_element_type=jnp.float32)
    if mode == "int8":        # per-token activations, per-channel weights
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif mode == "fp8":
        x = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        w = w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    elif mode != "f32":
        raise ValueError(f"unknown matmul mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    """x [T, heads, D] at positions 0..T-1; rotate (x[:D/2], x[D/2:])."""
    T, _, D = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


@partial(jax.jit, static_argnames=("n_heads", "n_kv", "head_dim", "eps",
                                   "theta", "mode"))
def layer_forward(x, w, *, n_heads, n_kv, head_dim, eps, theta, mode="f32"):
    """One decoder layer on x [T, H] (float32)."""
    T = x.shape[0]
    h = _rms_norm(x, w["attn_norm"], eps)
    q = _mm(h, w["wq"], mode).reshape(T, n_heads, head_dim)
    k = _mm(h, w["wk"], mode).reshape(T, n_kv, head_dim)
    v = _mm(h, w["wv"], mode).reshape(T, n_kv, head_dim)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = n_heads // n_kv
    qg = q.reshape(T, n_kv, rep, head_dim)
    outs = []
    for s in range(0, T, Q_BLOCK):
        e = min(s + Q_BLOCK, T)
        sc = jnp.einsum("sgrd,tgd->grst", qg[s:e], k[:e],
                        precision=HIGHEST) / np.sqrt(head_dim)
        mask = (jnp.arange(e)[None, :] <= jnp.arange(s, e)[:, None])
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("grst,tgd->sgrd", p, v[:e],
                               precision=HIGHEST))
    a = jnp.concatenate(outs, 0).reshape(T, n_heads * head_dim)
    x = x + _mm(a, w["wo"], mode)
    h = _rms_norm(x, w["mlp_norm"], eps)
    g = _mm(h, w["w_gate"], mode)
    u = _mm(h, w["w_up"], mode)
    return x + _mm(jax.nn.silu(g) * u, w["w_down"], mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def head_forward(x, final_norm, lm_head, *, eps, mode="f32"):
    return _mm(_rms_norm(x, final_norm, eps), lm_head, mode)


def _layer_kw(cfg):
    D = cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]
    return dict(n_heads=cfg["num_attention_heads"],
                n_kv=cfg["num_key_value_heads"], head_dim=D,
                eps=float(cfg["rms_norm_eps"]), theta=float(cfg["rope_theta"]))


def logits_at(weights: Dict[str, jax.Array], cfg: dict, tokens: Sequence[int],
              positions: Sequence[int], pad_to: int = 0,
              mode: str = "f32") -> np.ndarray:
    """Float32 logits [len(positions), vocab] of the model over ``tokens``
    at ``positions``. Right-padded to ``pad_to`` (causal: the pad cannot
    reach back), so one compiled shape serves every sequence of a cell."""
    T = max(len(tokens), pad_to)
    ids = np.zeros((T,), np.int32)
    ids[:len(tokens)] = tokens
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(
        jnp.float32)
    kw = _layer_kw(cfg)
    for i in range(cfg["num_hidden_layers"]):
        p = f"layers.{i}."
        w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
        x = layer_forward(x, w, mode=mode, **kw)
    head = weights.get("lm_head")
    if head is None:
        head = weights["embed"].T
    pos = np.zeros((_round_up(len(positions), 256),), np.int32)
    pos[:len(positions)] = positions
    lg = head_forward(jnp.take(x, jnp.asarray(pos), axis=0),
                      weights["final_norm"], head, eps=kw["eps"], mode=mode)
    return np.asarray(lg[:len(positions)], np.float32)


def _round_up(n, m):
    return -(-n // m) * m


def served_gaps(weights, cfg, prompt: Sequence[int], served: Sequence[int],
                pad_to: int = 0, mode: str = "f32"):
    """For one finished request: how far each SERVED token's reference logit
    lies below the reference's best at that position. Returns
    ``(gaps [n], ref_logits [n, V])``."""
    seq = list(prompt) + list(served)
    pos = list(range(len(prompt) - 1, len(seq) - 1))
    lg = logits_at(weights, cfg, seq, pos, pad_to=pad_to, mode=mode)
    gaps = lg.max(-1) - lg[np.arange(len(served)), np.asarray(served)]
    return gaps, lg
