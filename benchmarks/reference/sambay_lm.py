"""Plain reference of Phi-4-mini-flash-reasoning (SambaY, a decoder-hybrid-
decoder): Mamba-1 layers, differential attention with a sliding window, one
full differential-attention layer, and a cross-decoder of gated memory units
and cross-attention layers that read the full layer's keys and values.

Straightforward ``jax.numpy`` in float32 with "highest" matmul precision: no
kernels, no cache, no batching, nothing imported from ``paddle_tpu``. One
sequence at a time, layer by layer (one jitted function per layer kind,
compiled once per padded length), one layer's weights upcast at a time, so
that it fits beside the model's bf16 weights. The recurrence is a
``lax.scan`` over the tokens; attention is the full score matrix with the
causal mask (and the window) applied to it, a block of query rows at a time.

Sources: the model's ``config.json`` (the configuration file's ``_source``),
arXiv:2507.06607 (the architecture), arXiv:2410.05258 (differential
attention), arXiv:2312.00752 (Mamba). What the ``config.json`` does not
state is listed under ``assumed`` in the configuration file.

**The equations.** Every layer ``i``: ``x += Mixer_i(LN(x))``; ``x +=
W_down(silu(W_gate h) * W_up h)``, ``h = LN(x)``; LayerNorm with weight and
bias; final LayerNorm; logits through the embedding (tied). No positions are
encoded anywhere. With ``L`` layers and ``half = L / 2``: ``i`` even and ``<=
half``: Mamba; ``i`` odd and ``< half``: window attention; ``i = half + 1``:
full attention; ``i`` odd and above: cross attention (own ``W_q``, ``W_o``; K
and V are layer ``half + 1``'s); ``i`` even and ``> half``: GMU, ``W_2(m_t *
silu(W_1 u_t))`` with ``m_t`` layer ``half``'s scan output (before its gate)
at the same token.

Mamba: ``(x, z) = W_in u``; ``x = silu(conv4(x) + b)`` (causal, depthwise);
``(delta, B, C) = W_x x``; ``dt = softplus(W_dt delta + b_dt)``; ``A =
-exp(A_log)``; ``h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t) B_t``; ``y_t = h_t
C_t + D x_t``; out ``= W_out(y_t * silu(z_t))``. ``A_log`` and ``h`` are held
``[d_state, d_inner]``.

**Differential attention and the head pairing** (a layout convention; the
program uses the same): ADJACENT heads pair. With ``2P`` query heads and
``2G`` kv heads of width ``d``: ``q1 = heads 0,2,4..``, ``q2 = heads 1,3,5..``
(pair ``p`` is heads ``2p, 2p+1``); ``k1 = kv heads 0,2,..``, ``k2 = kv heads
1,3,..``; the value of kv pair ``g`` is ``(v head 2g | v head 2g+1)``, ``2d``
wide; differential head ``p`` reads kv pair ``p // (P/G)``. ``lam =
exp(lq1.lk1) - exp(lq2.lk2) + lam_init``, ``lam_init = 0.8 - 0.6 exp(-0.3
i)``. ``O = (softmax(q1 k1^T / sqrt d) - lam softmax(q2 k2^T / sqrt d)) V``,
causal (and ``j > t - window`` in window layers); ``O = RMSNorm_2d(O) * (1 -
lam_init)``; then ``W_o``.

``mode`` selects the arithmetic, for the CONTROLS of the correctness check:
``"f32"`` is the reference; ``"bf16"``, ``"int8"`` and ``"fp8"`` compute every
matrix product in that precision (float32 accumulation); ``"state_bf16"``
keeps everything in float32 but rounds the SSM state ``h`` to bfloat16 after
every token — what a cache that stored the state in the KV cache's type
would do.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 512          # query rows per attention block (memory, not maths)
HEAD_ROWS = 512        # logits rows per block
VOCAB_BLOCK = 32768    # embedding rows upcast at a time


def _fake_int8(x, axis):
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(x / s).clip(-127, 127) * s


def _mm(x, w, mode: str):
    """x [T, in] @ w [in, out] in the arithmetic ``mode`` names."""
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
    elif mode not in ("f32", "state_bf16"):
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def layer_kinds(cfg):
    L = cfg["num_hidden_layers"]
    half = L // 2
    out = []
    for i in range(L):
        if i % 2 == 0:
            out.append("mamba" if i <= half else "gmu")
        elif i < half:
            out.append("window")
        else:
            out.append("full" if i == half + 1 else "cross")
    return out


def sizes(cfg) -> dict:
    """The widths the layers are built from, published and assumed."""
    H = cfg["hidden_size"]
    ssm = cfg.get("ssm", {})
    return {"H": H, "F": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"],
            "d": H // cfg["num_attention_heads"],
            "di": int(ssm.get("expand", 2)) * H,
            "S": int(ssm.get("d_state", 16)), "K": int(ssm.get("d_conv", 4)),
            "r": int(ssm.get("dt_rank", H // 16)),
            "window": int(cfg["sliding_window"]),
            "eps": float(cfg["layer_norm_eps"])}


def lambda_init(layer_idx: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_idx)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)
            + b.astype(jnp.float32))


def _mlp(x, w, eps, mode):
    h = _ln(x, w["norm2_w"], w["norm2_b"], eps)
    return x + _mm(jax.nn.silu(_mm(h, w["w_gate"], mode))
                   * _mm(h, w["w_up"], mode), w["w_down"], mode)


@partial(jax.jit, static_argnames=("S", "K", "r", "eps", "mode"))
def mamba_layer(x, w, n, *, S, K, r, eps, mode="f32"):
    """x [T, H] -> (x', y [T, di], h [S, di]): the layer's output, its scan
    output before the gate (the memory, where this is the last Mamba layer)
    and the state after the first ``n`` tokens (the rest is padding)."""
    f = jnp.float32
    T = x.shape[0]
    u = _ln(x, w["norm1_w"], w["norm1_b"], eps)
    xz = _mm(u, w["in_proj"], mode)
    di = xz.shape[1] // 2
    xs, z = xz[:, :di], xz[:, di:]
    pad = jnp.concatenate([jnp.zeros((K - 1, di), f), xs])
    cw = w["conv_w"].astype(f)
    xs = jax.nn.silu(sum(pad[k:k + T] * cw[k] for k in range(K))
                     + w["conv_b"].astype(f))
    dbc = _mm(xs, w["x_proj"], mode)
    dt = jax.nn.softplus(_mm(dbc[:, :r], w["dt_proj"], mode)
                         + w["dt_bias"].astype(f))
    Bm, Cm = dbc[:, r:r + S], dbc[:, r + S:]
    A = -jnp.exp(w["A_log"].astype(f))                    # [S, di]
    D = w["D"].astype(f)

    def step(carry, inp):
        h, kept = carry
        t, xt, dtt, bt, ct = inp
        h = jnp.exp(dtt[None] * A) * h + (dtt * xt)[None] * bt[:, None]
        if mode == "state_bf16":
            # (not ``astype``: XLA:TPU drops a convert pair as excess
            # precision, and the control then rounds nothing)
            h = jax.lax.reduce_precision(h, exponent_bits=8, mantissa_bits=7)
        kept = jnp.where(t == n - 1, h, kept)
        return (h, kept), jnp.sum(h * ct[:, None], axis=0) + D * xt

    h0 = jnp.zeros((S, di), f)
    (_, h_n), y = jax.lax.scan(step, (h0, h0),
                               (jnp.arange(T), xs, dt, Bm, Cm))
    out = _mm(y * jax.nn.silu(z), w["out_proj"], mode)
    return _mlp(x + out, w, eps, mode), y, h_n


def _diff_attention(q, k, v, lam, lam0, subln, window, d, eps):
    """q [T, 2P, d]; k, v [T, 2G, d] -> [T, P*2d]. The full score matrix,
    ``Q_BLOCK`` query rows at a time (one compiled block, mapped)."""
    T = q.shape[0]
    q1, q2 = q[:, 0::2], q[:, 1::2]                       # [T, P, d]
    k1, k2 = k[:, 0::2], k[:, 1::2]                       # [T, G, d]
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], -1)    # [T, G, 2d]
    P, G = q1.shape[1], k1.shape[1]
    nb = -(-T // Q_BLOCK)

    def blocks(x):                                        # rows in blocks
        x = jnp.pad(x, ((0, nb * Q_BLOCK - T), (0, 0), (0, 0)))
        return x.reshape(nb, Q_BLOCK, G, P // G, d)

    j = jnp.arange(T)[None, :]

    def block(args):
        s, qa, qb = args
        i = s + jnp.arange(Q_BLOCK)[:, None]
        mask = j <= i
        if window:
            mask = mask & (j > i - window)

        def probs(qq, kk):
            sc = jnp.einsum("sgrd,tgd->grst", qq, kk,
                            precision=HIGHEST) / math.sqrt(d)
            return jax.nn.softmax(jnp.where(mask[None, None], sc, -jnp.inf),
                                  axis=-1)

        a = probs(qa, k1) - lam * probs(qb, k2)
        return jnp.einsum("grst,tgd->sgrd", a, vv, precision=HIGHEST)

    o = jax.lax.map(block, (jnp.arange(nb) * Q_BLOCK, blocks(q1), blocks(q2)))
    o = o.reshape(nb * Q_BLOCK, G, P // G, 2 * d)[:T]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = o * subln.astype(jnp.float32) * (1.0 - lam0)
    return o.reshape(T, P * 2 * d)


@partial(jax.jit, static_argnames=("heads", "kv_heads", "d", "window", "eps",
                                   "mode"))
def attention_layer(x, w, kv, lam0, *, heads, kv_heads, d, window, eps,
                    mode="f32"):
    """Window (``window`` > 0), full (0), or cross (``kv`` given: the full
    layer's projected keys and values) differential attention; ``lam0`` is
    the layer's ``lambda_init`` (an operand, so that the layers of one kind
    share one compiled function). Returns (x', (k, v) as projected here)."""
    T = x.shape[0]
    u = _ln(x, w["norm1_w"], w["norm1_b"], eps)
    q = _mm(u, w["wq"], mode).reshape(T, heads, d)
    if kv is None:
        kv = (_mm(u, w["wk"], mode).reshape(T, kv_heads, d),
              _mm(u, w["wv"], mode).reshape(T, kv_heads, d))
    f = jnp.float32
    lam = (jnp.exp(jnp.sum(w["lambda_q1"].astype(f) * w["lambda_k1"].astype(f)))
           - jnp.exp(jnp.sum(w["lambda_q2"].astype(f)
                             * w["lambda_k2"].astype(f))) + lam0)
    o = _diff_attention(q, kv[0], kv[1], lam, lam0, w["subln"], window, d,
                        eps)
    return _mlp(x + _mm(o, w["wo"], mode), w, eps, mode), kv


@partial(jax.jit, static_argnames=("eps", "mode"))
def gmu_layer(x, w, mem, *, eps, mode="f32"):
    u = _ln(x, w["norm1_w"], w["norm1_b"], eps)
    out = _mm(mem * jax.nn.silu(_mm(u, w["w1"], mode)), w["w2"], mode)
    return _mlp(x + out, w, eps, mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def head_forward(x, norm_w, norm_b, embed, *, eps, mode="f32"):
    """Logits of rows x [R, H] through the tied embedding [V, H], a block
    of the vocabulary at a time."""
    h = _ln(x, norm_w, norm_b, eps)
    V = embed.shape[0]
    return jnp.concatenate([_mm(h, embed[s:s + VOCAB_BLOCK].T, mode)
                            for s in range(0, V, VOCAB_BLOCK)], -1)


def hidden_states(weights: Dict[str, jax.Array], cfg: dict,
                  tokens: Sequence[int], pad_to: int = 0, mode: str = "f32",
                  states: Dict[int, jax.Array] = None):
    """Float32 hidden states [T, H] after the last layer (before the final
    norm). Right-padded to ``pad_to``: everything is causal, so the pad
    cannot reach back. With ``states`` (a dict to fill): stop after the last
    Mamba layer and leave there, by layer index, the SSM state ``[d_state,
    d_inner]`` once ``tokens`` are consumed."""
    z = sizes(cfg)
    T = max(len(tokens), pad_to)
    ids = np.zeros((T,), np.int32)
    ids[:len(tokens)] = tokens
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(
        jnp.float32)
    mem = kv = None
    kinds = layer_kinds(cfg)
    if states is not None:
        kinds = kinds[:len(kinds) - kinds[::-1].index("mamba")]
    for i, kind in enumerate(kinds):
        p = f"layers.{i}."
        w = {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}
        if kind == "mamba":
            x, mem, h = mamba_layer(x, w, jnp.int32(len(tokens)), S=z["S"],
                                    K=z["K"], r=z["r"], eps=z["eps"],
                                    mode=mode)
            if states is not None:
                states[i] = h
        elif kind == "gmu":
            x = gmu_layer(x, w, mem, eps=z["eps"], mode=mode)
        else:
            x, own = attention_layer(
                x, w, kv if kind == "cross" else None,
                jnp.float32(lambda_init(i)), heads=z["heads"],
                kv_heads=z["kv_heads"], d=z["d"],
                window=z["window"] if kind == "window" else 0,
                eps=z["eps"], mode=mode)
            if kind == "full":
                kv = own
    return x


def logits_at(weights: Dict[str, jax.Array], cfg: dict, tokens: Sequence[int],
              positions: Sequence[int], pad_to: int = 0,
              mode: str = "f32") -> np.ndarray:
    """Float32 logits [len(positions), vocab] of the model over ``tokens``
    at ``positions``."""
    x = hidden_states(weights, cfg, tokens, pad_to, mode)
    eps = sizes(cfg)["eps"]
    out = []
    for s in range(0, len(positions), HEAD_ROWS):
        pos = np.zeros((HEAD_ROWS,), np.int32)
        blk = positions[s:s + HEAD_ROWS]
        pos[:len(blk)] = blk
        lg = head_forward(jnp.take(x, jnp.asarray(pos), axis=0),
                          weights["final_norm_w"], weights["final_norm_b"],
                          weights["embed"], eps=eps, mode=mode)
        out.append(np.asarray(lg[:len(blk)], np.float32))
    return np.concatenate(out, 0)


def states_at(weights, cfg, tokens: Sequence[int], pad_to: int = 0,
              mode: str = "f32") -> Dict[int, np.ndarray]:
    """The float32 SSM state ``[d_state, d_inner]`` of every Mamba layer, by
    layer index, after the model has consumed ``tokens``."""
    states: Dict[int, jax.Array] = {}
    hidden_states(weights, cfg, tokens, pad_to, mode, states)
    return {i: np.asarray(h, np.float32) for i, h in states.items()}


def slow_elements(weights, cfg, layer: int, tokens: float) -> np.ndarray:
    """Mask ``[d_state, d_inner]`` of the state elements of Mamba layer
    ``layer`` that remember at least ``tokens`` tokens: ``dt0 * A <= 1 /
    tokens`` with ``dt0 = softplus(dt_bias)``, the channel's step before the
    input moves it, and ``A = exp(A_log)``. These are the elements in which
    a rounding made at every token piles up while the noise of the inputs
    averages out, and which a state dropped hundreds of tokens ago still
    shows in."""
    p = f"layers.{layer}."
    dt0 = np.logaddexp(0.0, np.asarray(weights[p + "dt_bias"], np.float32))
    A = np.exp(np.asarray(weights[p + "A_log"], np.float32))
    return dt0[None, :] * A <= 1.0 / float(tokens)


def state_drift(h: np.ndarray, h_ref: np.ndarray, mask: np.ndarray) -> float:
    """``|h - h_ref| / |h_ref|`` (Euclidean) over the elements of ``mask``."""
    d = (np.asarray(h, np.float64) - h_ref)[mask]
    return float(np.linalg.norm(d) / np.linalg.norm(h_ref[mask]))


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
