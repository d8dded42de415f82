"""Plain reference of a decoder whose attention layers are window and full
attention mixed, gated and QK-normed, with dense layers first and then many
sigmoid-routed experts beside a shared one (Trinity's ``afmoe`` layer), given
ONE CHIP'S SHARE of the routed experts.

Straightforward ``jax.numpy`` in float32 with "highest" matmul precision: no
kernels, no cache, no ring, no batching, nothing imported from
``paddle_tpu``. One sequence at a time, a layer at a time, the queries in
blocks of one compiled body (so that a sequence of 18k tokens fits and
compiles in seconds); each held expert runs on the rows routed to it
(gathered; its weights upcast one expert at a time).

For a token's hidden state ``x`` at position ``t`` (``x_0 = sqrt(hidden) *
E[token]``), ``u = RMSNorm(x)``:

- ``q, k, v, g = u W_q, u W_k, u W_v, u W_g``; q and k normed per head
  (RMSNorm over ``head_dim``, their own weights);
- window layers: q and k rotated by rope at ``t`` (half-split pairs,
  ``rope_theta``, the whole head); a query sees the last ``sliding_window``
  positions, itself included. Full layers: no position term at all;
- scores ``q.k / sqrt(head_dim)``, causal, softmax; ``o = (p v) *
  sigmoid(g)``; ``x += RMSNorm(o W_o)``;
- ``w = RMSNorm(x)``; dense layers ``SwiGLU(w)``; expert layers: ``s =
  sigmoid(w W_r)`` over ALL published experts, the ``top_k`` of ``s + b``
  chosen (``b`` the selection bias, for the choice only), weights ``s / sum
  of the chosen s * route_scale``, summed over the chosen experts that are
  HELD (``experts_held = [lo, hi)``) — what the absent experts would add is
  left out — plus the shared expert; ``x += RMSNorm(f)``;
- ``logits = RMSNorm(x) W_head``.

Departures from the published model code, none of which moves a number
here: the route normalisation's ``+ 1e-20`` is left out (the sum of eight
sigmoid scores is never below 1e-12 at these weights, and 1e-20 is below
float32's resolution of any such sum); the router's scores are computed in
float32 from the float32 hidden state.

It routes for itself: it never takes the program's expert choices.

``mode`` selects the arithmetic or plants a fault, for the CONTROLS of the
correctness check: ``"f32"`` is the reference; ``"bf16"``, ``"int8"``
compute every matrix product in that precision; the plants (:data:`FAULTS`)
compute in float32 with one thing wrong — ``window_all`` window layers
attend the whole context, ``rope_full`` rope in the full layers too,
``no_rope`` no rope in the window layers, ``no_gate`` no output gate,
``no_qknorm`` no q/k norms, ``scale_1`` ``route_scale`` 1, ``no_mup`` no
input scale, ``no_post_norm`` the two post-norms left out, ``top7`` one
expert fewer, ``no_shared`` the shared expert left out.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # query rows per attention block (memory, not maths)
FAULTS = ("window_all", "rope_full", "no_rope", "no_gate", "no_qknorm",
          "scale_1", "no_mup", "no_post_norm", "top7", "no_shared")


def sizes(cfg: dict) -> dict:
    """The sizes the layers need, under short names. ``E`` is the router's
    width (the PUBLISHED number of routed experts), ``held`` the half-open
    range of experts whose weights are here."""
    pub = cfg.get("published", {})
    E = int(pub.get("num_experts", cfg["num_experts"]))
    lo, hi = cfg.get("experts_held", [0, cfg["num_experts"]])
    return {"H": cfg["hidden_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "d": cfg["head_dim"],
            "Fd": cfg["intermediate_size"], "F": cfg["moe_intermediate_size"],
            "Fs": cfg["moe_intermediate_size"] * cfg["num_shared_experts"],
            "dense": cfg["num_dense_layers"], "E": E,
            "held": (int(lo), int(hi)), "k": cfg["num_experts_per_tok"],
            "route_norm": bool(cfg.get("route_norm", True)),
            "route_scale": float(cfg.get("route_scale", 1.0)),
            "W": cfg["sliding_window"], "theta": float(cfg["rope_theta"]),
            "mup": bool(cfg.get("mup_enabled", False)),
            "eps": float(cfg["rms_norm_eps"])}


def layer_kinds(cfg: dict):
    """"window" / "full" of each of the ``num_hidden_layers`` layers run
    (the first of the published ``layer_types``)."""
    kinds = {"sliding_attention": "window", "full_attention": "full"}
    return [kinds[t] for t in cfg["layer_types"][:cfg["num_hidden_layers"]]]


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
    elif mode != "f32" and mode not in FAULTS:
        raise ValueError(f"unknown mode {mode!r}")
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta: float):
    """x [T, heads, d] at positions 0..T-1: rotate the pairs (j, j + d/2)
    by ``t * theta^(-2j/d)``."""
    T, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _swiglu(h, wg, wu, wd, mode):
    return _mm(jax.nn.silu(_mm(h, wg, mode)) * _mm(h, wu, mode), wd, mode)


def attention_part(u, w, z: dict, kind: str, mode: str = "f32"):
    """Gated, QK-normed attention over u [T, H] of a ``kind`` layer, the
    queries in blocks of :data:`Q_BLOCK` (one block body, mapped): a full
    layer's block against every key under the causal mask, a window layer's
    against the ``sliding_window - 1`` keys before it and its own."""
    T = u.shape[0]
    nh, G, d, W = z["heads"], z["kv_heads"], z["d"], z["W"]
    q = _mm(u, w["wq"], mode).reshape(T, nh, d)
    k = _mm(u, w["wk"], mode).reshape(T, G, d)
    v = _mm(u, w["wv"], mode).reshape(T, G, d)
    g = _mm(u, w["wg"], mode)
    if mode != "no_qknorm":
        q = _rms_norm(q, w["q_norm"], z["eps"])
        k = _rms_norm(k, w["k_norm"], z["eps"])
    window = kind == "window" and mode != "window_all"
    if (kind == "window" and mode != "no_rope") or mode == "rope_full":
        q, k = _rope(q, z["theta"]), _rope(k, z["theta"])
    n = -(-T // Q_BLOCK)
    pad = n * Q_BLOCK - T
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        n, Q_BLOCK, G, nh // G, d)
    front = W - 1 if window else 0
    kp = jnp.pad(k, ((front, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((front, pad), (0, 0), (0, 0)))
    L = front + Q_BLOCK if window else n * Q_BLOCK

    def block(b):
        s0 = b * Q_BLOCK
        at = s0 if window else 0                  # first key row in kp
        kb = jax.lax.dynamic_slice_in_dim(kp, at, L, 0)
        vb = jax.lax.dynamic_slice_in_dim(vp, at, L, 0)
        kpos = at - front + jnp.arange(L)
        qpos = s0 + jnp.arange(Q_BLOCK)
        mask = (kpos[None] >= 0) & (kpos[None] <= qpos[:, None])
        if window:
            mask = mask & (kpos[None] > qpos[:, None] - W)
        sc = jnp.einsum("sgrd,tgd->grst", qb[b], kb,
                        precision=HIGHEST) / math.sqrt(d)
        p = jax.nn.softmax(jnp.where(mask[None, None], sc, -jnp.inf), -1)
        return jnp.einsum("grst,tgd->sgrd", p, vb,
                          precision=HIGHEST).reshape(Q_BLOCK, nh * d)

    o = jax.lax.map(block, jnp.arange(n)).reshape(n * Q_BLOCK, nh * d)[:T]
    if mode != "no_gate":
        o = o * jax.nn.sigmoid(g)
    return _mm(o, w["wo"], mode)


def route(h, w, z: dict, mode: str = "f32"):
    """[T, E] routing weights over ALL published experts: zero where an
    expert was not chosen."""
    s = jax.nn.sigmoid(_mm(h, w["router"], mode))
    k = z["k"] - 1 if mode == "top7" else z["k"]
    _, idx = jax.lax.top_k(s + w["router_bias"].astype(jnp.float32), k)
    chosen = jnp.sum(jax.nn.one_hot(idx, z["E"], dtype=jnp.float32), axis=1)
    wts = s * chosen
    if z["route_norm"]:
        wts = wts / jnp.sum(wts, axis=-1, keepdims=True)
    return wts * (1.0 if mode == "scale_1" else z["route_scale"])


@partial(jax.jit, static_argnames=("cap", "mode"))
def _held_experts(h, wts, wg, wu, wd, *, cap: int, mode: str = "f32"):
    """sum over the held experts e of ``wts[:, e] * SwiGLU_e(h)`` for h [T,
    H], each expert run on the ``cap`` rows with the largest weight for it
    (every row it was chosen by: ``cap`` is at least the busiest expert's
    count)."""
    def one(y, xs):
        wg_e, wu_e, wd_e, col = xs
        top, rows = jax.lax.top_k(col, cap)
        part = _swiglu(jnp.take(h, rows, axis=0), wg_e, wu_e, wd_e, mode)
        return y.at[rows].add(top[:, None] * part), None

    y, _ = jax.lax.scan(one, jnp.zeros_like(h), (wg, wu, wd, wts.T))
    return y


def expert_part(h, w, z: dict, mode: str = "f32", held=None,
                with_shared: bool = True, real=None):
    """What this share adds for h [T, H]: the chosen experts in ``held``
    (default: the configuration's), weighted, and the shared expert. Rows
    at or past ``real`` (padding) route nowhere."""
    lo, hi = z["held"] if held is None else held
    wts = _route(h, w, zs=_static(z), mode=mode)[:, lo:hi]
    if real is not None:
        wts = jnp.where(jnp.arange(h.shape[0])[:, None] < real, wts, 0.0)
    busiest = int(np.asarray(jnp.max(jnp.sum(wts > 0, axis=0))))
    cap = min(h.shape[0], 1 << max(busiest - 1, 0).bit_length())
    y = _held_experts(h, wts, w["w_gate_e"], w["w_up_e"], w["w_down_e"],
                      cap=max(cap, 1), mode=mode)
    if with_shared and mode != "no_shared":
        y = y + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"], mode)
    return y


@partial(jax.jit, static_argnames=("zs", "mode"))
def _route(h, w, *, zs, mode):
    return route(h, w, dict(zs), mode)


def _static(z: dict):
    return tuple(sorted((k, v) for k, v in z.items()))


@partial(jax.jit, static_argnames=("zs", "kind", "mode"))
def _attend(x, w, *, zs, kind, mode):
    z = dict(zs)
    a = attention_part(_rms_norm(x, w["attn_norm"], z["eps"]), w, z, kind,
                       mode)
    if mode != "no_post_norm":
        a = _rms_norm(a, w["post_attn_norm"], z["eps"])
    return x + a, _rms_norm(x + a, w["pre_mlp_norm"], z["eps"])


@partial(jax.jit, static_argnames=("mode",))
def _dense_ffn(h, w, *, mode):
    return _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], mode)


@partial(jax.jit, static_argnames=("eps", "mode"))
def _add_ffn(x, f, norm_w, *, eps, mode):
    return x + (f if mode == "no_post_norm" else _rms_norm(f, norm_w, eps))


def layer_forward(x, w, z: dict, i: int, kind: str, mode: str = "f32",
                  real=None):
    """Layer ``i`` (of ``kind``) on x [T, H] (float32)."""
    zs = _static(z)
    x, h = _attend(x, w, zs=zs, kind=kind, mode=mode)
    if i < z["dense"]:
        f = _dense_ffn(h, w, mode=mode)
    else:
        f = expert_part(h, w, z, mode, real=real)
    return _add_ffn(x, f, w["post_mlp_norm"], eps=z["eps"], mode=mode)


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
    reach back, and it routes nowhere)."""
    z = sizes(cfg)
    T = max(len(tokens), pad_to)
    ids = np.zeros((T,), np.int32)
    ids[:len(tokens)] = tokens
    x = jnp.take(weights["embed"], jnp.asarray(ids), axis=0).astype(
        jnp.float32)
    if z["mup"] and mode != "no_mup":
        x = x * math.sqrt(z["H"])
    for i, kind in enumerate(layer_kinds(cfg)):
        x = layer_forward(x, layer_weights(weights, i), z, i, kind, mode,
                          real=len(tokens))
    pos = np.zeros((-(-len(positions) // 256) * 256,), np.int32)
    pos[:len(positions)] = positions
    lg = head_forward(jnp.take(x, jnp.asarray(pos), axis=0),
                      weights["final_norm"], weights["lm_head"],
                      eps=z["eps"], mode=mode)
    return np.asarray(lg[:len(positions)], np.float32)


def served_gaps(weights, cfg, prompt: Sequence[int], served: Sequence[int],
                pad_to: int = 0, mode: str = "f32", from_pos: int = 0):
    """For one finished request: how far each SERVED token predicted at a
    position ``>= from_pos`` lies, in the reference's logits, below the
    reference's best at that position. Returns ``(gaps [n], ref_logits [n,
    V], the served tokens compared [n])``."""
    seq = list(prompt) + list(served)
    first = max(len(prompt) - 1, from_pos)
    pos = list(range(first, len(seq) - 1))
    got = np.asarray(seq[first + 1:], np.int64)
    if not pos:
        return np.zeros((0,)), np.zeros((0, sizes(cfg)["V"])), got
    lg = logits_at(weights, cfg, seq, pos, pad_to=pad_to, mode=mode)
    gaps = lg.max(-1) - lg[np.arange(len(pos)), got]
    return gaps, lg, got
