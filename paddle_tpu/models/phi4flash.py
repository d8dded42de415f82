"""Phi-4-mini-flash-reasoning (SambaY): a decoder-hybrid-decoder.

Sources: the model's ``config.json``
(huggingface.co/microsoft/Phi-4-mini-flash-reasoning), its report
(arXiv:2507.06607, "Decoder-Hybrid-Decoder Architecture..."), Differential
Transformer (arXiv:2410.05258) and Mamba (arXiv:2312.00752).

Every layer ``i`` is ``x += Mixer_i(LN(x)); x += SwiGLU(LN(x))`` with
LayerNorm (weight and bias), no positional encoding anywhere, a final
LayerNorm and a head tied to the embedding. The mixers, for ``L`` layers and
``half = L // 2`` (published: 32 and 16):

- ``mamba``  (``i`` even, ``i <= half``): Mamba-1 selective scan. Layer
  ``half``'s scan output ``y_t`` (before its gate) is the MEMORY ``m_t``.
- ``window`` (``i`` odd, ``i < half``): differential attention over the last
  ``sliding_window`` positions.
- ``full``   (``i == half + 1``): differential attention over everything; the
  ONLY layer whose K/V grow with the context.
- ``cross``  (``i`` odd, ``i > half + 1``): differential attention with its
  own ``W_q``/``W_o`` only; K and V are layer ``half + 1``'s cache.
- ``gmu``    (``i`` even, ``i > half``): gated memory unit,
  ``W_2(m_t * silu(W_1 u_t))`` with ``m_t`` of the same token.

Nothing above layer ``half + 1`` keeps state, so prompt positions whose
logits nobody needs run layers ``0..half+1`` only (the published design).

**Differential attention, and the head pairing used here.** With ``2P`` query
heads and ``2G`` kv heads of ``d`` (published: 40, 20, 64), ADJACENT heads form
a pair: differential head ``p`` is ``(q1, q2) = (head 2p, head 2p+1)``, kv
pair ``g = p // (P/G)`` is ``(k1, k2) = (kv head 2g, 2g+1)`` and its value is
``(v head 2g | v head 2g+1)``, ``2d`` wide. ``O_p = (softmax(q1 k1^T/sqrt d) -
lam * softmax(q2 k2^T/sqrt d)) V``, then RMSNorm over ``2d`` and ``(1 -
lam_init)``; ``lam = exp(lq1.lk1) - exp(lq2.lk2) + lam_init``, ``lam_init =
0.8 - 0.6 exp(-0.3 i)`` with ``i`` the layer's index. With that pairing a
projection's output reshaped to ``2d``-wide rows IS the packed pair, and the
cache holds ``(k1|k2)`` and ``(v1|v2)`` rows of 128 — the paged attention
kernel's lane width — in HEAD-MAJOR blocks ``(G, block_size, 2d)``: the 10
packed kv heads of the published model are no sublane multiple, and a
token-major block would be padded to 16 of them in HBM
(``inference/cache_spec.py``, ``layout="head"``). The two softmaxes run as
two query rows ``(q1|0)`` and
``(0|q2)`` of one ordinary attention over those rows (the zero halves cancel
the other key), so every attention of this model is the same kernel as a
dense decoder's; the difference is taken afterwards.

The serving surface is the one ``inference/executor.py`` calls
(``paged_decode_step`` / ``paged_prefill_chunk`` on ``.model``) and is built
from :meth:`Phi4FlashForCausalLM.cache_spec`: each layer receives the view of
the cache kind it declared. ``forward`` / ``generate`` are the cache-free
fixture the serving tests compare against.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor
from ..nn.layer_base import Layer
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..ops.paged_attention import masked_attention

__all__ = ["Phi4FlashConfig", "Phi4FlashForCausalLM", "phi4flash_tiny_config",
           "layer_kinds"]


@dataclasses.dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    tie_word_embeddings: bool = True
    # not in the published config.json: Mamba-1's defaults, and dt_rank =
    # hidden / 16 as in the Mamba reference implementation
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: Optional[int] = None
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.dt_rank is None:
            self.dt_rank = self.hidden_size // 16
        L = self.num_hidden_layers
        if self.mb_per_layer != 2 or L % 4 or L < 8:
            raise ValueError("the SambaY layout written down here alternates "
                             "Mamba with attention (mb_per_layer 2) over a "
                             "multiple of 4 layers, at least 8")
        if not self.tie_word_embeddings:
            raise ValueError("Phi-4-mini-flash ties its head to the embedding")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.hidden_size


def phi4flash_tiny_config(**kw) -> Phi4FlashConfig:
    """All five layer kinds, one period of each decoder, a window shorter
    than the test sequences; float32."""
    return Phi4FlashConfig(**{**dict(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=8, num_attention_heads=8, num_key_value_heads=4,
        sliding_window=24, max_position_embeddings=4096, d_state=16,
        dtype="float32"), **kw})


def layer_kinds(cfg: Phi4FlashConfig):
    half = cfg.num_hidden_layers // 2
    out = []
    for i in range(cfg.num_hidden_layers):
        if i % 2 == 0:
            out.append("mamba" if i <= half else "gmu")
        elif i < half:
            out.append("window")
        else:
            out.append("full" if i == half + 1 else "cross")
    return out


# ------------------------------------------------------------------ plain math
def _layer_norm(x, w, b, eps):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(x.dtype)


def lambda_init(layer_idx: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_idx)


def _pack_q(q, d):
    """(B, W, 2P*d) projection -> (B, W, 2P, 2d) rows ``(q1|0)``, ``(0|q2)``
    per differential head, scaled so that the attention's ``1/sqrt(2d)``
    comes out as the model's ``1/sqrt(d)``."""
    B, W, n = q.shape
    qp = q.reshape(B, W, n // (2 * d), 2, d)
    eye = jnp.eye(2, dtype=q.dtype) * jnp.asarray(math.sqrt(2.0), q.dtype)
    return jnp.einsum("bwpcd,ce->bwpced", qp, eye).reshape(
        B, W, n // d, 2 * d)


def _diff_combine(out, lam, lam0, subln_w, eps):
    """(B, W, 2P, 2d) attention rows -> (B, W, P*2d): first minus ``lam``
    times second, RMSNorm over 2d, times ``1 - lam_init``."""
    B, W, H, D = out.shape
    o = out.astype(jnp.float32).reshape(B, W, H // 2, 2, D)
    o = o[:, :, :, 0] - lam * o[:, :, :, 1]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
    o = o * subln_w.astype(jnp.float32) * (1.0 - lam0)
    return o.reshape(B, W, (H // 2) * D).astype(out.dtype)


def _val(p):
    return p.value


def _normal(std):
    def init(shape, dtype):
        from ..nn.initializer import Normal

        return Normal(0.0, std)(shape, dtype)
    return init


# ---------------------------------------------------------------------- mixers
class _DiffAttention(Layer):
    """window / full / cross differential attention; ``own_kv`` False for
    cross (no K/V projections, reads the full layer's pool)."""

    def __init__(self, cfg, layer_idx, kind):
        super().__init__()
        self.cfg, self.kind, self.idx = cfg, kind, layer_idx
        h, d = cfg.hidden_size, cfg.head_dim
        nq, nkv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
        init = _normal(0.02)
        self.q_proj = Linear(h, nq, bias_attr=False, weight_attr=init)
        if kind != "cross":
            self.k_proj = Linear(h, nkv, bias_attr=False, weight_attr=init)
            self.v_proj = Linear(h, nkv, bias_attr=False, weight_attr=init)
        self.o_proj = Linear(nq, h, bias_attr=False, weight_attr=init)
        for n in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"):
            setattr(self, n, self.create_parameter(
                [d], default_initializer=_normal(0.1)))
        from ..nn.initializer import Constant

        self.subln_weight = self.create_parameter(
            [2 * d], default_initializer=Constant(1.0))
        self.lam0 = lambda_init(layer_idx)

    def _lam(self):
        f = jnp.float32
        a = jnp.sum(_val(self.lambda_q1).astype(f) * _val(self.lambda_k1).astype(f))
        b = jnp.sum(_val(self.lambda_q2).astype(f) * _val(self.lambda_k2).astype(f))
        return jnp.exp(a) - jnp.exp(b) + self.lam0

    def _q(self, u):
        return _pack_q(u @ _val(self.q_proj.weight), self.cfg.head_dim)

    def _kv(self, u):
        B, W, _ = u.shape
        D = 2 * self.cfg.head_dim
        k = (u @ _val(self.k_proj.weight)).reshape(B, W, -1, D)
        v = (u @ _val(self.v_proj.weight)).reshape(B, W, -1, D)
        return k, v

    def _out(self, o):
        o = _diff_combine(o, self._lam(), self.lam0,
                          _val(self.subln_weight), self.cfg.layer_norm_eps)
        return o @ _val(self.o_proj.weight)

    # -- cache-free (fixture): u (B, T, H); kv = the full layer's (k, v)
    def dense(self, u, kv=None):
        T = u.shape[1]
        if self.kind != "cross":
            kv = self._kv(u)
        i, j = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
        mask = j <= i
        if self.kind == "window":
            mask = mask & (j > i - self.cfg.sliding_window)
        o = masked_attention(self._q(u), kv[0], kv[1],
                             jnp.broadcast_to(mask, (u.shape[0], T, T)))
        return self._out(o), kv

    # -- decode: one token for every slot
    def decode(self, u, view, ctx):
        from ..ops import paged_attention as pa

        q = self._q(u)                                     # (B, 1, 2P, 2d)
        pos = ctx["pos"]
        if self.kind == "window":
            kp, vp = view
            k, v = self._kv(u)
            kp, vp = pa.write_ring_kv(kp, vp, k[:, 0], v[:, 0],
                                      ctx["ring_tables"], pos,
                                      head_major=True)
            o = pa.paged_window_attention(q, kp, vp, ctx["ring_tables"], pos,
                                          self.cfg.sliding_window,
                                          head_major=True)
            return self._out(o), (kp, vp)
        kp, vp = view
        if self.kind == "full":
            k, v = self._kv(u)
            kp, vp = pa.write_decode_kv(kp, vp, k[:, 0], v[:, 0],
                                        ctx["tables"], pos, head_major=True)
        o = pa.paged_decode_attention(q, kp, vp, ctx["tables"], pos,
                                      head_major=True)
        return self._out(o), (kp, vp)

    # -- prefill: one chunk of one slot (window / full)
    def chunk(self, u, view, ctx):
        from ..ops import paged_attention as pa

        q = self._q(u)                                     # (1, C, 2P, 2d)
        k, v = self._kv(u)
        kp, vp = view
        start = ctx["start"]
        if self.kind == "full":
            kp, vp = pa.write_chunk_kv(kp, vp, k[0], v[0], ctx["table"],
                                       start, head_major=True)
            o = pa.paged_prefill_attention(q, kp, vp, ctx["table"], start,
                                           head_major=True)
            return self._out(o), (kp, vp)
        # window: attend the ring's past and the chunk side by side, then
        # overwrite the ring (ops/paged_attention.py)
        o, kp, vp = pa.window_chunk_attention(
            q, k[0], v[0], kp, vp, ctx["ring_row"], start, ctx["n_valid"],
            self.cfg.sliding_window, head_major=True)
        return self._out(o), (kp, vp)


class _Mamba(Layer):
    def __init__(self, cfg):
        super().__init__()
        self.cfg = cfg
        h, di, S, K, r = (cfg.hidden_size, cfg.d_inner, cfg.d_state,
                          cfg.d_conv, cfg.dt_rank)
        init = _normal(0.02)
        self.in_proj = Linear(h, 2 * di, bias_attr=False, weight_attr=init)
        self.conv_weight = self.create_parameter(
            [K, di], default_initializer=_normal(0.5))
        self.conv_bias = self.create_parameter([di], is_bias=True)
        self.x_proj = Linear(di, r + 2 * S, bias_attr=False, weight_attr=init)
        self.dt_proj = Linear(r, di, bias_attr=False, weight_attr=init)
        self.dt_bias = self.create_parameter(
            [di], default_initializer=lambda s, t: jnp.log(jnp.expm1(jnp.exp(
                jnp.linspace(math.log(1e-3), math.log(1e-1), s[0])))
            ).astype(t))
        # (d_state, d_inner): channels on the lanes (ops/selective_scan.py)
        self.A_log = self.create_parameter(
            [S, di], default_initializer=lambda s, t: jnp.broadcast_to(
                jnp.log(jnp.arange(1, s[0] + 1, dtype=jnp.float32))[:, None],
                s).astype(t))
        from ..nn.initializer import Constant

        self.D = self.create_parameter([di],
                                       default_initializer=Constant(1.0))
        self.out_proj = Linear(di, h, bias_attr=False, weight_attr=init)

    def _split(self, u):
        di = self.cfg.d_inner
        xz = u @ _val(self.in_proj.weight)
        return xz[..., :di], xz[..., di:]

    def _dbc(self, xc, dtype):
        """conv output (.., di) f32 -> (dt (.., di), B, C (.., S)), f32."""
        r, S = self.cfg.dt_rank, self.cfg.d_state
        f = jnp.float32
        dbc = xc.astype(dtype) @ _val(self.x_proj.weight)
        dt = dbc[..., :r] @ _val(self.dt_proj.weight)
        dt = jax.nn.softplus(dt.astype(f) + _val(self.dt_bias).astype(f))
        return dt, dbc[..., r:r + S].astype(f), dbc[..., r + S:].astype(f)

    def _A(self):
        return -jnp.exp(_val(self.A_log).astype(jnp.float32))

    def _finish(self, y, z):
        g = y * jax.nn.silu(z.astype(jnp.float32))
        return g.astype(z.dtype) @ _val(self.out_proj.weight)

    def dense(self, u):
        from ..ops import selective_scan as ss

        f = jnp.float32
        xs, z = self._split(u)                              # (B, T, di)
        K = self.cfg.d_conv
        tail = jnp.zeros((K - 1, xs.shape[-1]), xs.dtype)

        def one(xb):
            xc, _ = ss.causal_conv_chunk(tail, xb, _val(self.conv_weight),
                                         _val(self.conv_bias), 0)
            xc = jax.nn.silu(xc)
            dt, Bm, Cm = self._dbc(xc, u.dtype)
            h0 = jnp.zeros((self.cfg.d_state, xs.shape[-1]), f)
            return ss.ssm_chunk_scan_ref(xc, dt, self._A(), Bm, Cm,
                                         _val(self.D).astype(f), h0)[0]

        y = jax.vmap(one)(xs)
        return self._finish(y, z), y

    def decode(self, u, view, ctx):
        from ..ops import selective_scan as ss

        f = jnp.float32
        tail, h = view
        act = ctx["active"]
        xs, z = self._split(u[:, 0])                        # (B, di)
        xc, new_tail = ss.causal_conv_step(tail, xs, _val(self.conv_weight),
                                           _val(self.conv_bias))
        xc = jax.nn.silu(xc)
        dt, Bm, Cm = self._dbc(xc, u.dtype)
        # a row that is idle or prefilling keeps its state: dt 0, tail kept
        dt = dt * (act > 0)[:, None]
        new_tail = jnp.where((act > 0)[:, None, None], new_tail, tail)
        y, h = ss.ssm_step(xc, dt, self._A(), Bm, Cm, _val(self.D).astype(f),
                           h)
        return self._finish(y, z)[:, None], (new_tail, h), y[:, None]

    def chunk(self, u, view, ctx):
        from ..ops import selective_scan as ss

        f = jnp.float32
        tail, h = view
        slot, n_valid = ctx["slot"], ctx["n_valid"]
        fresh = ctx["start"] == 0        # a request's first chunk: zero state
        tail0 = jnp.where(fresh, 0, tail[slot])
        h0 = jnp.where(fresh, 0.0, h[slot])
        xs, z = self._split(u[0])                           # (C, di)
        xc, new_tail = ss.causal_conv_chunk(
            tail0, xs, _val(self.conv_weight), _val(self.conv_bias), n_valid)
        xc = jax.nn.silu(xc)
        dt, Bm, Cm = self._dbc(xc, u.dtype)
        dt = jnp.where((jnp.arange(xs.shape[0]) < n_valid)[:, None], dt, 0.0)
        y, hT = ss.ssm_chunk_scan(xc, dt, self._A(), Bm, Cm,
                                  _val(self.D).astype(f), h0)
        view = (tail.at[slot].set(new_tail), h.at[slot].set(hT))
        return self._finish(y, z)[None], view, y[None]


class _GMU(Layer):
    def __init__(self, cfg):
        super().__init__()
        init = _normal(0.02)
        self.in_proj = Linear(cfg.hidden_size, cfg.d_inner, bias_attr=False,
                              weight_attr=init)
        self.out_proj = Linear(cfg.d_inner, cfg.hidden_size, bias_attr=False,
                               weight_attr=init)

    def forward(self, u, mem):
        g = jax.nn.silu((u @ _val(self.in_proj.weight)).astype(jnp.float32))
        return (mem * g).astype(u.dtype) @ _val(self.out_proj.weight)


class _MLP(Layer):
    def __init__(self, cfg):
        super().__init__()
        init = _normal(0.02)
        h, f = cfg.hidden_size, cfg.intermediate_size
        self.gate_proj = Linear(h, f, bias_attr=False, weight_attr=init)
        self.up_proj = Linear(h, f, bias_attr=False, weight_attr=init)
        self.down_proj = Linear(f, h, bias_attr=False, weight_attr=init)

    def forward(self, u):
        return (jax.nn.silu(u @ _val(self.gate_proj.weight))
                * (u @ _val(self.up_proj.weight))) @ _val(self.down_proj.weight)


class _Norm(Layer):
    def __init__(self, cfg):
        super().__init__()
        from ..nn.initializer import Constant

        self.weight = self.create_parameter(
            [cfg.hidden_size], default_initializer=Constant(1.0))
        self.bias = self.create_parameter([cfg.hidden_size], is_bias=True)
        self._eps = cfg.layer_norm_eps

    def forward(self, x):
        return _layer_norm(x, _val(self.weight), _val(self.bias), self._eps)


class Phi4FlashLayer(Layer):
    def __init__(self, cfg, idx, kind):
        super().__init__()
        self.kind = kind
        self.input_layernorm = _Norm(cfg)
        self.post_attention_layernorm = _Norm(cfg)
        self.mixer = (_Mamba(cfg) if kind == "mamba" else
                      _GMU(cfg) if kind == "gmu" else
                      _DiffAttention(cfg, idx, kind))
        self.mlp = _MLP(cfg)

    def _mlp(self, x):
        return x + self.mlp(self.post_attention_layernorm(x))

    def run(self, mode, x, view, ctx, mem, shared_view):
        """One layer in ``mode`` ("decode" | "chunk"). Returns (x, the
        layer's new view, memory)."""
        u = self.input_layernorm(x)
        if self.kind == "mamba":
            a, view, mem = getattr(self.mixer, mode)(u, view, ctx)
        elif self.kind == "gmu":
            a = self.mixer(u, mem)
        elif self.kind == "cross":
            a, _ = self.mixer.decode(u, shared_view, ctx)
        else:
            a, view = getattr(self.mixer, mode)(u, view, ctx)
        return self._mlp(x + a), view, mem


class Phi4FlashModel(Layer):
    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.cfg = cfg
        from ..framework.dtype import convert_dtype

        dtype = None if cfg.dtype == "float32" else convert_dtype(cfg.dtype)

        def cast(layer):
            # one float32 block at a time (see LlamaModel)
            if dtype is not None:
                layer._convert_dtype(dtype)
            return layer

        self.kinds = layer_kinds(cfg)
        self.half = cfg.num_hidden_layers // 2
        self.embed_tokens = cast(Embedding(cfg.vocab_size, cfg.hidden_size))
        self.layers = LayerList([cast(Phi4FlashLayer(cfg, i, k))
                                 for i, k in enumerate(self.kinds)])
        self.norm = cast(_Norm(cfg))

    def _embed(self, ids):
        return jnp.take(_val(self.embed_tokens.weight), ids, axis=0)

    # ----------------------------------------------------------- cache-free
    def forward(self, input_ids):
        x = self._embed(input_ids.value if isinstance(input_ids, Tensor)
                        else input_ids)
        mem = kv = None
        for layer in self.layers:
            u = layer.input_layernorm(x)
            if layer.kind == "mamba":
                a, y = layer.mixer.dense(u)
                mem = y
            elif layer.kind == "gmu":
                a = layer.mixer(u, mem)
            elif layer.kind == "cross":
                a, _ = layer.mixer.dense(u, kv)
            else:
                a, own = layer.mixer.dense(u)
                if layer.kind == "full":
                    kv = own
            x = layer._mlp(x + a)
        return Tensor(self.norm(x))

    # -------------------------------------------------------------- serving
    def _ring_tables(self, views, rows):
        """Block ids of each row's window ring: slot ``b`` owns blocks
        ``1 + b*R .. (b+1)*R`` of every window layer's pool (block 0 is
        scratch)."""
        from ..ops.paged_attention import slot_ring_tables

        for k, v in zip(self.kinds, views):
            if k == "window":
                # pool = scratch block + max_batch rings (executor.py)
                R = -(-self.cfg.sliding_window // v[0].shape[2]) + 1
                return slot_ring_tables(rows, R)
        return None

    def paged_decode_step(self, token, views, block_tables, pos, lora=None,
                          active=None):
        """token (B, 1), one row per slot; ``views[i]``: layer i's cache as
        its spec declared it — ``(K, V)`` block pool (full), ``(K, V)`` ring
        pool (window), both head-major ``(N, G, bs, 2d)``, ``(conv tail, ssm state)`` slot arrays (mamba), ``()``
        otherwise; ``active`` (B,) masks the rows that decode this tick.
        Returns (final-normed hidden (B, 1, H), new views)."""
        if lora is not None:
            raise NotImplementedError("no LoRA path for this class")
        B = pos.shape[0]
        active = jnp.ones((B,), jnp.int32) if active is None else active
        views = [tuple(t.value for t in v) for v in views]
        ring = self._ring_tables(views, jnp.arange(B))
        ctx = {"tables": block_tables, "pos": pos, "active": active,
               "ring_tables": (None if ring is None else
                               jnp.where((active > 0)[:, None], ring, 0))}
        x = self._embed(token.value)
        x, new = self._run("decode", x, views, ctx, 0,
                           self.cfg.num_hidden_layers, None)[:2]
        return Tensor(self.norm(x)), [tuple(Tensor(t) for t in v)
                                      for v in new]

    def _run(self, mode, x, views, ctx, lo, hi, mem):
        new = list(views)
        for i in range(lo, hi):
            layer = self.layers[i]
            shared = new[self.half + 1] if layer.kind == "cross" else None
            x, new[i], mem = layer.run(mode, x, new[i], ctx, mem, shared)
        return x, new, mem

    def paged_prefill_chunk(self, input_ids, views, block_table, start,
                            lora=None, last_idx=0, slot=None):
        """One prompt chunk (1, C) of the request in ``slot`` = int32
        ``(slot index, valid tokens in the chunk, 1 on the request's last
        chunk)``. Layers ``0..half+1`` run over the whole chunk; the layers
        above keep no state, so they run on the token at ``last_idx`` alone
        and only on the last chunk. Returns (final-normed hidden (1, 1, H)
        of that token, new views)."""
        if lora is not None:
            raise NotImplementedError("no LoRA path for this class")
        s, n_valid, final = slot[0], slot[1], slot[2]
        views = [tuple(t.value for t in v) for v in views]
        ring = self._ring_tables(views, s[None])
        ctx = {"table": block_table, "start": start, "slot": s,
               "n_valid": n_valid,
               "ring_row": None if ring is None else ring[0]}
        x = self._embed(input_ids.value)
        lo = self.half + 2
        x, new, mem = self._run("chunk", x, views, ctx, 0, lo, None)
        x1 = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, 1)
        m1 = jax.lax.dynamic_slice_in_dim(mem, last_idx, 1, 1)
        dctx = {"tables": block_table[None], "pos": (start + last_idx)[None],
                "active": jnp.ones((1,), jnp.int32), "ring_tables": None}

        def upper():
            return self.norm(self._run("decode", x1, new, dctx, lo,
                                       self.cfg.num_hidden_layers, m1)[0])

        h = jax.lax.cond(final > 0, upper, lambda: jnp.zeros_like(x1))
        # (the barrier keeps XLA from sinking the caller's head matmul into
        # the conditional, whose output then is a copy of the embedding:
        # 1 GB of temps in the compiled chunk program)
        h = jax.lax.optimization_barrier(h)
        return Tensor(h), [tuple(Tensor(t) for t in v) for v in new]


class Phi4FlashForCausalLM(Layer):
    def __init__(self, cfg: Phi4FlashConfig):
        super().__init__()
        self.cfg = cfg
        self.model = Phi4FlashModel(cfg)

    def cache_spec(self):
        """The per-layer cache declaration the serving engine is built
        from (inference/cache_spec.py)."""
        from ..framework.dtype import convert_dtype
        from ..inference import cache_spec as cs

        cfg = self.cfg
        G, D = cfg.num_key_value_heads // 2, 2 * cfg.head_dim
        dtype = jnp.zeros((), convert_dtype(cfg.dtype)).dtype
        full_at = cfg.num_hidden_layers // 2 + 1
        per = {"mamba": cs.state([
            ("conv", (cfg.d_conv - 1, cfg.d_inner), dtype),
            ("ssm", (cfg.d_state, cfg.d_inner), np.float32)]),
            "window": cs.window(cfg.sliding_window, G, D, layout="head"),
            "full": cs.full(G, D, layout="head"),
            "cross": cs.shared(full_at),
            "gmu": cs.none()}
        return cs.CacheSpec([per[k] for k in layer_kinds(cfg)], dtype)

    def logits(self, h):
        return h @ _val(self.model.embed_tokens.weight).T

    def forward(self, input_ids):
        return Tensor(self.logits(self.model(input_ids).value))

    def generate(self, input_ids, max_new_tokens: int = 32):
        """Greedy generation WITHOUT a cache: the whole (padded) sequence is
        run again for every token. The fixture the serving tests compare
        with; not a serving path."""
        from ..jit import functional_call, state_values

        # (a test fixture that syncs a token at a time by design)
        ids = np.asarray(  # graftlint: noqa[host-sync]
            input_ids.value if isinstance(input_ids, Tensor) else input_ids,
            np.int32)
        B, P = ids.shape
        buf = np.zeros((B, P + max_new_tokens), np.int32)
        buf[:, :P] = ids
        params = state_values(self)

        @jax.jit
        def step(p, seq, at):
            lg = functional_call(self, p, Tensor(seq)).value
            row = jax.lax.dynamic_slice_in_dim(lg, at, 1, 1)[:, 0]
            return jnp.argmax(row.astype(jnp.float32), -1).astype(jnp.int32)

        for t in range(P, P + max_new_tokens):
            buf[:, t] = np.asarray(  # graftlint: noqa[host-sync]
                step(params, jnp.asarray(buf), t - 1))
        return Tensor(jnp.asarray(buf))
