"""Plain reference of a decoder with latent attention (MLA) and a mixture of
many routed experts beside a shared one (DeepSeek-V3's layer, as
Mistral-Small-4 configures it), given ONE CHIP'S SHARE of the routed experts.

Straightforward ``jax.numpy`` in float32 with "highest" matmul precision: no
kernels, no cache, no batching, nothing imported from ``paddle_tpu``. One
sequence at a time, a layer at a time (one small jitted function per padded
length; the held experts are walked by a scan that upcasts one expert's
weights at a time, so that a layer of 1.7 GB in bfloat16 is never held in
float32).

The layer, for a token's hidden state ``x`` at position ``t`` (RMSNorm
before each half, a residual around each):

- queries ``c_q = RMSNorm(W_dq h)``, ``q = W_uq c_q`` -> heads x [nope | rope];
- the latent row ``[c | k_r] = W_dkv h``, ``c = RMSNorm(c)``, ``k_r =
  RoPE_t(k_r)``: ONE rope key a token, shared by all heads;
- keys and values in the EXPANDED form: ``[k_nope_i | v_i] = W_ukv,i c``;
- rotation of interleaved pairs (2j, 2j+1) at YaRN's frequencies
  (:func:`yarn_inv_freq`); cos and sin carry no factor (``mscale`` =
  ``mscale_all_dim``);
- scores ``(q_nope.k_nope + q_rope.k_r) * sigma * a_t``, causal, softmax in
  float32; ``sigma = (nope + rope)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``; ``a_t = 1 + beta * ln(1 + floor(t / original_max))``
  (``llama_4_scaling_beta``);
- experts: ``s = sigmoid(W_g h)`` over ALL published experts in float32, the
  ``top_k`` of ``s + b`` chosen, weights ``s / sum of the chosen s`` (times
  ``routed_scaling_factor``); the sum runs over the chosen experts that are
  HELD (``experts_held = [lo, hi)``) only — what the absent experts would
  have added is left out — plus the shared expert.

It routes for itself: it never takes the program's expert choices.

``mode`` selects the arithmetic or plants a fault, for the CONTROLS of the
correctness check: ``"f32"`` is the reference; ``"bf16"``, ``"int8"``,
``"fp8"`` compute every matrix product in that precision; ``"drop_1.25"``
drops the tokens an expert gets over a capacity of 1.25 x the even share of
each call of 256 rows;
``"top3"`` routes to one expert fewer; ``"no_shared"`` leaves the shared
expert out; ``"k_unrotated"`` caches the rope key unrotated; ``"no_qscale"``
leaves ``a_t`` at 1.
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
FAULTS = ("drop_1.25", "top3", "no_shared", "k_unrotated", "no_qscale")
DROP_ROWS = 256        # rows a call of the capacity-bucket plant joins


def sizes(cfg: dict) -> dict:
    """The sizes the layer needs, under short names. ``E`` is the router's
    width (the PUBLISHED number of routed experts), ``held`` the half-open
    range of experts whose weights are here."""
    pub = cfg.get("published", {})
    E = int(pub.get("n_routed_experts", cfg["n_routed_experts"]))
    lo, hi = cfg.get("experts_held", [0, cfg["n_routed_experts"]])
    rp = cfg["rope_parameters"]
    return {"H": cfg["hidden_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "heads": cfg["num_attention_heads"],
            "rq": cfg["q_lora_rank"], "rkv": cfg["kv_lora_rank"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "F": cfg["moe_intermediate_size"],
            "E": E, "held": (int(lo), int(hi)),
            "k": cfg["num_experts_per_tok"],
            "n_shared": cfg["n_shared_experts"],
            "norm_topk": bool(cfg.get("norm_topk_prob", True)),
            "route_scale": float(cfg.get("routed_scaling_factor", 1.0)),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(rp["rope_theta"]), "factor": float(rp["factor"]),
            "orig": int(rp["original_max_position_embeddings"]),
            "beta_fast": float(rp["beta_fast"]),
            "beta_slow": float(rp["beta_slow"]),
            "mscale_all_dim": float(rp.get("mscale_all_dim", 0.0)),
            "qscale_beta": float(rp.get("llama_4_scaling_beta", 0.0))}


def yarn_inv_freq(dr: int, theta: float, factor: float, orig: int,
                  beta_fast: float, beta_slow: float) -> np.ndarray:
    """YaRN's per-pair frequencies over ``dr`` rope dims: the plain ones
    where a pair turns more than ``beta_fast`` times in ``orig`` positions,
    the plain ones over ``factor`` where it turns fewer than ``beta_slow``
    times, a linear ramp between."""
    j = np.arange(dr // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / dr)

    def corr(turns):
        return dr * math.log(orig / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    lo = max(math.floor(corr(beta_fast)), 0)
    hi = min(math.ceil(corr(beta_slow)), dr - 1)
    if hi == lo:
        hi += 0.001
    r = np.clip((j - lo) / (hi - lo), 0.0, 1.0)
    return (f * (1 - r) + f / factor * r).astype(np.float32)


def softmax_scale(z: dict) -> float:
    m = (0.1 * z["mscale_all_dim"] * math.log(z["factor"]) + 1.0
         if z["factor"] > 1 and z["mscale_all_dim"] else 1.0)
    return (z["dn"] + z["dr"]) ** -0.5 * m * m


def query_scale(pos, beta: float, orig: int):
    """``a_t`` of every position in ``pos`` (float32)."""
    return 1.0 + beta * jnp.log1p(jnp.floor(pos.astype(jnp.float32) / orig))


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


def _rope_interleaved(x, inv_freq):
    """x [T, ..., dr] at positions 0..T-1; rotate the pairs (2j, 2j+1)."""
    T = x.shape[0]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    shape = (T,) + (1,) * (x.ndim - 2) + (-1,)
    c, s = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * c - x2 * s, x2 * c + x1 * s], -1).reshape(x.shape)


def route(h, w, z: dict, mode: str = "f32"):
    """[T, E] routing weights over ALL published experts: zero where an
    expert was not chosen."""
    s = jax.nn.sigmoid(_mm(h, w["router"], mode))
    k = z["k"] - 1 if mode == "top3" else z["k"]
    _, idx = jax.lax.top_k(s + w["router_bias"].astype(jnp.float32), k)
    chosen = jnp.sum(jax.nn.one_hot(idx, z["E"], dtype=jnp.float32), axis=1)
    wts = s * chosen
    if z["norm_topk"]:
        wts = wts / jnp.sum(wts, axis=-1, keepdims=True)
    wts = wts * z["route_scale"]
    if mode == "drop_1.25":
        # as a server with capacity buckets would run the sequence: calls
        # of DROP_ROWS rows, in each a bucket of ceil(1.25 x the even share)
        # rows an expert, filled in token order; what lands past it is
        # dropped
        T = h.shape[0]
        rows = min(DROP_ROWS, T)
        cap = math.ceil(1.25 * rows * k / z["E"])
        before = jnp.cumsum(chosen, axis=0) - chosen
        rank = before - before[jnp.arange(T) // rows * rows]
        wts = jnp.where(rank < cap, wts, 0.0)
    return wts


def _swiglu(h, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(h, wg, mode)) * _mm(h, wu, mode), wd, mode)


def expert_part(h, w, z: dict, mode: str = "f32", held=None,
                with_shared: bool = True):
    """What this share adds for h [T, H]: the chosen experts in ``held``
    (default: the configuration's), weighted, and the shared expert."""
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


def attention_part(h, w, z: dict, inv_freq, mode: str = "f32"):
    """Latent attention over h [T, H] in the expanded form."""
    T = h.shape[0]
    nh, dn, dr, dv, rkv = z["heads"], z["dn"], z["dr"], z["dv"], z["rkv"]
    cq = _rms_norm(_mm(h, w["w_dq"], mode), w["q_norm"], z["eps"])
    q = _mm(cq, w["w_uq"], mode).reshape(T, nh, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope_interleaved(q[..., dn:], inv_freq)
    ckr = _mm(h, w["w_dkv"], mode)
    c = _rms_norm(ckr[:, :rkv], w["kv_norm"], z["eps"])
    k_r = ckr[:, rkv:]
    if mode != "k_unrotated":
        k_r = _rope_interleaved(k_r, inv_freq)
    kv = _mm(c, w["w_ukv"], mode).reshape(T, nh, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    pos = jnp.arange(T)
    a = (jnp.ones((T,), jnp.float32) if mode == "no_qscale"
         else query_scale(pos, z["qscale_beta"], z["orig"]))
    scale = softmax_scale(z) * a                            # [T]
    outs = []
    for s in range(0, T, Q_BLOCK):
        e = min(s + Q_BLOCK, T)
        sc = (jnp.einsum("shd,thd->hst", q_nope[s:e], k_nope[:e],
                         precision=HIGHEST)
              + jnp.einsum("shd,td->hst", q_rope[s:e], k_r[:e],
                           precision=HIGHEST)) * scale[None, s:e, None]
        mask = pos[None, :e] <= pos[s:e, None]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hst,thd->shd", p, v[:e], precision=HIGHEST))
    o = jnp.concatenate(outs, 0).reshape(T, nh * dv)
    return _mm(o, w["wo"], mode)


def _static(z: dict):
    return tuple(sorted((k, v) for k, v in z.items()))


@partial(jax.jit, static_argnames=("zs", "mode"))
def layer_forward(x, w, inv_freq, *, zs, mode="f32"):
    """One decoder layer on x [T, H] (float32)."""
    z = dict(zs)
    x = x + attention_part(_rms_norm(x, w["attn_norm"], z["eps"]), w, z,
                           inv_freq, mode)
    return x + expert_part(_rms_norm(x, w["mlp_norm"], z["eps"]), w, z, mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def head_forward(x, final_norm, lm_head, *, eps, mode="f32"):
    return _mm(_rms_norm(x, final_norm, eps), lm_head, mode)


def layer_weights(weights: Dict[str, jax.Array], i: int) -> dict:
    p = f"layers.{i}."
    return {k[len(p):]: v for k, v in weights.items() if k.startswith(p)}


def logits_at(weights: Dict[str, jax.Array], cfg: dict, tokens: Sequence[int],
              positions: Sequence[int], pad_to: int = 0,
              mode: str = "f32") -> np.ndarray:
    """Float32 logits [len(positions), vocab] of the model over ``tokens``
    at ``positions``. Right-padded to ``pad_to`` (causal: the pad cannot
    reach back; ``drop_1.25`` fills its buckets in token order, call by
    call, so the pad cannot push a real token out either)."""
    z = sizes(cfg)
    T = max(len(tokens), pad_to)
    ids = np.zeros((T,), np.int32)
    ids[:len(tokens)] = tokens
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(
        jnp.float32)
    inv = jnp.asarray(yarn_inv_freq(z["dr"], z["theta"], z["factor"],
                                    z["orig"], z["beta_fast"],
                                    z["beta_slow"]))
    zs = _static(z)
    for i in range(z["L"]):
        x = layer_forward(x, layer_weights(weights, i), inv, zs=zs, mode=mode)
    pos = np.zeros((-(-len(positions) // 256) * 256,), np.int32)
    pos[:len(positions)] = positions
    lg = head_forward(jnp.take(x, jnp.asarray(pos), axis=0),
                      weights["final_norm"], weights["lm_head"],
                      eps=z["eps"], mode=mode)
    return np.asarray(lg[:len(positions)], np.float32)


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
