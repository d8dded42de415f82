"""Plain reference of a decoder of Mamba-2 layers with an attention layer
every so often and, in EVERY layer, many routed experts beside a shared MLP
(Granite 4.0-H, ``model_type`` granitemoehybrid), given ONE CHIP'S SHARE of
the routed experts.

Straightforward ``jax.numpy`` in float32 with "highest" matmul precision: no
kernels, no cache, no batching, nothing imported from ``paddle_tpu``. One
sequence at a time, a layer at a time (one jitted function per layer kind and
padded length; the held experts are walked by a scan that upcasts one
expert's weights at a time). The recurrence is the SEQUENTIAL one, a
``lax.scan`` over the tokens — never the chunked form the program serves a
prompt with; attention is the full score matrix, a block of query rows at a
time.

Sources: the model's ``config.json`` (the configuration file's ``_source``);
the ``granitemoehybrid`` and ``bamba`` model code in Hugging Face
``transformers``; Mamba-2, arXiv:2405.21060. What the ``config.json`` does
not state is listed under ``assumed`` in the configuration file.

**The equations.** ``x_0 = embedding_multiplier * E[token]``. Layer ``l``,
``u = RMSNorm(x)`` (eps ``rms_norm_eps``, weight):

- Mamba-2 mixer: ``[z | xBC | dt] = u W_in`` (widths ``di | di + 2N |
  heads``, ``di = heads * head_dim``, ``N = d_state``); ``xBC = silu(conv(
  xBC) + b)`` (causal, depthwise, ``d_conv`` taps); ``[x | B | C] = xBC``
  (one group: B and C shared by all heads); ``dt = softplus(dt + dt_bias)``
  (no upper clamp); ``A = -exp(A_log)`` (one value a head); the state ``S[h]``
  (head_dim x N): ``S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] x_t[h]
  (x) B_t``; ``y_t[h] = S_t[h] C_t + D[h] x_t[h]``; gate THEN norm: ``g = y *
  silu(z)``, ``g = g * rsqrt(mean(g^2) + eps) * w`` over all ``di`` values;
  ``mixer = g W_out``.
- attention mixer: ``q = u W_q`` (heads x d), ``k = u W_k``, ``v = u W_v``
  (kv heads x d), no bias, **no rotation and no position term**; scores ``q.k
  * attention_multiplier``, causal softmax, grouped; ``mixer = o W_o``.
- ``x += residual_multiplier * mixer``.
- expert block, ``v = RMSNorm(x)``: router logits ``r = v W_r`` over ALL
  published experts; the ``k`` largest chosen; weights = softmax over those
  ``k`` logits only; the sum runs over the chosen experts that are HELD
  (``experts_held = [lo, hi)``) — what the absent experts would have added is
  left out — plus the shared MLP (its own width); ``x += residual_multiplier
  * (moe + shared)``.
- ``logits = RMSNorm(x) E^T / logits_scaling`` (tied head).

It routes for itself: it never takes the program's expert choices.

``mode`` selects the arithmetic or plants a fault, for the CONTROLS of the
correctness check: ``"f32"`` is the reference; ``"bf16"``, ``"int8"``,
``"fp8"`` compute every matrix product in that precision; ``"state_bf16"``
keeps everything in float32 but rounds the SSM state to bfloat16 after every
token; ``"top9"`` routes to one expert fewer; ``"softmax_all"`` takes the
weights from a softmax over ALL the router's logits; ``"no_shared"`` leaves
the shared MLP out; ``"res_1"`` leaves ``residual_multiplier`` at 1;
``"scale_sqrt"`` scales the scores by ``d^-1/2``; ``"rope"`` rotates q and k
(half-split pairs, theta 10000); ``"norm_then_gate"`` norms ``y`` before the
gate.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block (memory, not maths)
HEAD_ROWS = 256        # logits rows per block
FAULTS = ("state_bf16", "top9", "softmax_all", "no_shared", "res_1",
          "scale_sqrt", "rope", "norm_then_gate")


def sizes(cfg: dict) -> dict:
    """The sizes the layers need, under short names. ``E`` is the router's
    width (the PUBLISHED number of routed experts), ``held`` the half-open
    range of experts whose weights are here."""
    pub = cfg.get("published", {})
    E = int(pub.get("num_local_experts", cfg["num_local_experts"]))
    lo, hi = cfg.get("experts_held", [0, cfg["num_local_experts"]])
    H = cfg["hidden_size"]
    nh, P = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    return {"H": H, "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "d": H // cfg["num_attention_heads"],
            "mh": nh, "P": P, "di": nh * P, "N": cfg["mamba_d_state"],
            "K": cfg["mamba_d_conv"], "F": cfg["intermediate_size"],
            "Fs": cfg["shared_intermediate_size"], "E": E,
            "held": (int(lo), int(hi)), "k": cfg["num_experts_per_tok"],
            "eps": float(cfg["rms_norm_eps"]),
            "att_scale": float(cfg["attention_multiplier"]),
            "emb_scale": float(cfg["embedding_multiplier"]),
            "res_scale": float(cfg["residual_multiplier"]),
            "logit_div": float(cfg["logits_scaling"])}


def layer_kinds(cfg: dict):
    """'mamba' or 'attention' of each layer that is run: the first
    ``num_hidden_layers`` of the published list."""
    return list(cfg["layer_types"])[:cfg["num_hidden_layers"]]


def _fake_int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s).clip(-127, 127) * s


def _mm(x, w, mode: str):
    """x [T, in] @ w [in, out] in the arithmetic ``mode`` names (a planted
    fault computes in float32)."""
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if mode == "bf16":
        return jnp.matmul(x.astype(jnp.bfloat16), w.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    if mode == "int8":        # per-token activations, per-channel weights
        x, w = _fake_int8(x, -1), _fake_int8(w, 0)
    elif mode == "fp8":
        x = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
        w = w.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    elif mode != "f32" and mode not in FAULTS:
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


# ------------------------------------------------------------------ the mixers
def mamba_part(u, w, n, z: dict, mode: str = "f32"):
    """u [T, H] -> (the mixer's output [T, H], the state [heads, head_dim,
    N] after the first ``n`` tokens; the rest is padding)."""
    f = jnp.float32
    T = u.shape[0]
    di, N, K, mh, P = z["di"], z["N"], z["K"], z["mh"], z["P"]
    p = _mm(u, w["in_proj"], mode)
    zg, xbc, dt = p[:, :di], p[:, di:di + di + 2 * N], p[:, di + di + 2 * N:]
    pad = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), f), xbc])
    cw = w["conv_w"].astype(f)
    xbc = jax.nn.silu(sum(pad[k:k + T] * cw[k] for k in range(K))
                      + w["conv_b"].astype(f))
    x = xbc[:, :di].reshape(T, mh, P)
    Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"].astype(f))          # [T, heads]
    A = -jnp.exp(w["A_log"].astype(f))                         # [heads]
    D = w["D"].astype(f)

    def step(carry, inp):
        S, kept = carry
        t, xt, dtt, bt, ct = inp
        S = (jnp.exp(dtt * A)[:, None, None] * S
             + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        if mode == "state_bf16":
            # (not ``astype``: XLA:TPU drops a convert pair as excess
            # precision, and the control then rounds nothing)
            S = jax.lax.reduce_precision(S, exponent_bits=8, mantissa_bits=7)
        kept = jnp.where(t == n - 1, S, kept)
        y = jnp.sum(S * ct[None, None, :], axis=-1) + D[:, None] * xt
        return (S, kept), y

    S0 = jnp.zeros((mh, P, N), f)
    (_, S_n), y = jax.lax.scan(step, (S0, S0),
                               (jnp.arange(T), x, dt, Bm, Cm))
    y = y.reshape(T, di)
    gate = jax.nn.silu(zg)
    if mode == "norm_then_gate":
        g = _rms_norm(y, w["ssm_norm"], z["eps"]) * gate
    else:
        g = _rms_norm(y * gate, w["ssm_norm"], z["eps"])
    return _mm(g, w["out_proj"], mode), S_n


def _rotate_half(x, theta: float = 10000.0):
    """The planted rotation: x [T, heads, d] at positions 0..T-1, half-split
    pairs."""
    T, _, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention_part(u, w, z: dict, mode: str = "f32"):
    """Grouped-query attention over u [T, H], no positions."""
    T = u.shape[0]
    nh, G, d = z["heads"], z["kv_heads"], z["d"]
    q = _mm(u, w["wq"], mode).reshape(T, nh, d)
    k = _mm(u, w["wk"], mode).reshape(T, G, d)
    v = _mm(u, w["wv"], mode).reshape(T, G, d)
    if mode == "rope":
        q, k = _rotate_half(q), _rotate_half(k)
    scale = d ** -0.5 if mode == "scale_sqrt" else z["att_scale"]
    qg = q.reshape(T, G, nh // G, d)
    pos = jnp.arange(T)
    outs = []
    for s in range(0, T, Q_BLOCK):
        e = min(s + Q_BLOCK, T)
        sc = jnp.einsum("sgrd,tgd->grst", qg[s:e], k[:e],
                        precision=HIGHEST) * scale
        mask = pos[None, :e] <= pos[s:e, None]
        p = jax.nn.softmax(jnp.where(mask[None, None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("grst,tgd->sgrd", p, v[:e],
                               precision=HIGHEST))
    o = jnp.concatenate(outs, 0).reshape(T, nh * d)
    return _mm(o, w["wo"], mode)


# ------------------------------------------------------------- the expert block
def route(h, w, z: dict, mode: str = "f32"):
    """[T, E] routing weights over ALL published experts: zero where an
    expert was not chosen."""
    r = _mm(h, w["router"], mode)
    k = z["k"] - 1 if mode == "top9" else z["k"]
    top, idx = jax.lax.top_k(r, k)
    chosen = jnp.sum(jax.nn.one_hot(idx, z["E"], dtype=jnp.float32), axis=1)
    if mode == "softmax_all":
        return jax.nn.softmax(r, axis=-1) * chosen
    e = jnp.exp(r - top[:, :1]) * chosen
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _swiglu(h, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(h, wg, mode)) * _mm(h, wu, mode), wd, mode)


def expert_part(h, w, z: dict, mode: str = "f32", held=None,
                with_shared: bool = True):
    """What this share adds for h [T, H]: the chosen experts in ``held``
    (default: the configuration's), weighted, and the shared MLP."""
    lo, hi = z["held"] if held is None else held
    wts = route(h, w, z, mode)[:, lo:hi]                    # [T, held]

    def one(y, xs):
        wg, wu, wd, col = xs
        return y + col[:, None] * _swiglu(h, wg, wu, wd, mode), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                        (w["w_gate_e"], w["w_up_e"], w["w_down_e"], wts.T))
    if with_shared and mode != "no_shared":
        y = y + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], mode)
    return y


# ------------------------------------------------------------------ the layers
def _static(z: dict):
    return tuple(sorted((k, v) for k, v in z.items()))


@partial(jax.jit, static_argnames=("zs", "kind", "mode"))
def layer_forward(x, w, n, *, zs, kind, mode="f32"):
    """One layer on x [T, H] (float32). Returns (x', the SSM state after the
    first ``n`` tokens — zeros [1] for an attention layer)."""
    z = dict(zs)
    res = 1.0 if mode == "res_1" else z["res_scale"]
    u = _rms_norm(x, w["mix_norm"], z["eps"])
    if kind == "mamba":
        a, S = mamba_part(u, w, n, z, mode)
    else:
        a, S = attention_part(u, w, z, mode), jnp.zeros((1,), jnp.float32)
    x = x + res * a
    return x + res * expert_part(_rms_norm(x, w["mlp_norm"], z["eps"]), w, z,
                                 mode), S


@partial(jax.jit, static_argnames=("eps", "div", "mode"))
def head_forward(x, final_norm, embed, *, eps, div, mode="f32"):
    """Logits of rows x [R, H] through the tied embedding [V, H]."""
    return _mm(_rms_norm(x, final_norm, eps), embed.T, mode) / div


def layer_weights(weights: Dict[str, jax.Array], i: int) -> dict:
    p = f"layers.{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def hidden_states(weights: Dict[str, jax.Array], cfg: dict,
                  tokens: Sequence[int], pad_to: int = 0, mode: str = "f32",
                  states: Dict[int, jax.Array] = None):
    """Float32 hidden states [T, H] after the last layer (before the final
    norm). Right-padded to ``pad_to``: everything is causal, so the pad
    cannot reach back. With ``states`` (a dict to fill): leave there, by
    layer index, the SSM state of every Mamba-2 layer once ``tokens`` are
    consumed."""
    z = sizes(cfg)
    T = max(len(tokens), pad_to)
    ids = np.zeros((T,), np.int32)
    ids[:len(tokens)] = tokens
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(
        jnp.float32) * z["emb_scale"]
    zs = _static(z)
    for i, kind in enumerate(layer_kinds(cfg)):
        x, S = layer_forward(x, layer_weights(weights, i),
                             jnp.int32(len(tokens)), zs=zs, kind=kind,
                             mode=mode)
        if states is not None and kind == "mamba":
            states[i] = S
    return x


def logits_at(weights: Dict[str, jax.Array], cfg: dict, tokens: Sequence[int],
              positions: Sequence[int], pad_to: int = 0,
              mode: str = "f32") -> np.ndarray:
    """Float32 logits [len(positions), vocab] of the model over ``tokens``
    at ``positions``."""
    z = sizes(cfg)
    x = hidden_states(weights, cfg, tokens, pad_to, mode)
    out = []
    for s in range(0, len(positions), HEAD_ROWS):
        pos = np.zeros((HEAD_ROWS,), np.int32)
        blk = positions[s:s + HEAD_ROWS]
        pos[:len(blk)] = blk
        lg = head_forward(jnp.take(x, jnp.asarray(pos), axis=0),
                          weights["final_norm"], weights["embed"],
                          eps=z["eps"], div=z["logit_div"], mode=mode)
        out.append(np.asarray(lg[:len(blk)], np.float32))
    return np.concatenate(out, 0)


def states_at(weights, cfg, tokens: Sequence[int], pad_to: int = 0,
              mode: str = "f32") -> Dict[int, np.ndarray]:
    """The float32 SSM state ``[heads, head_dim, d_state]`` of every Mamba-2
    layer, by layer index, after the model has consumed ``tokens``."""
    states: Dict[int, jax.Array] = {}
    hidden_states(weights, cfg, tokens, pad_to, mode, states)
    return {i: np.asarray(S, np.float32) for i, S in states.items()}


def slow_heads(weights, cfg, layer: int, n: int) -> np.ndarray:
    """Mask ``[heads]`` of the ``n`` heads of Mamba-2 layer ``layer`` that
    remember longest: those of the least ``dt0 * A`` with ``dt0 =
    softplus(dt_bias)``, the head's step before the input moves it, and ``A =
    exp(A_log)`` (a head remembers about ``1 / (dt0 * A)`` tokens). These are
    the heads in which a rounding made at every token piles up while the
    noise of the inputs averages out, and which a state dropped hundreds of
    tokens ago still shows in. A COUNT and not a threshold on the memory: a
    seed's draw may leave no head at all under a threshold (three seeds of
    sixteen did, at 256 tokens: my chip runs, PR 35), and a mask of no head
    compares nothing."""
    p = f"layers.{layer}."
    dt0 = np.logaddexp(0.0, np.asarray(weights[p + "dt_bias"], np.float32))
    rate = dt0 * np.exp(np.asarray(weights[p + "A_log"], np.float32))
    mask = np.zeros(rate.shape, bool)
    mask[np.argsort(rate, kind="stable")[:int(n)]] = True
    return mask


def state_drift(S: np.ndarray, S_ref: np.ndarray, heads: np.ndarray) -> float:
    """``|S - S_ref| / |S_ref|`` (Euclidean) over the heads of ``heads``."""
    d = (np.asarray(S, np.float64) - S_ref)[heads]
    return float(np.linalg.norm(d) / np.linalg.norm(S_ref[heads]))


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
