"""Granite 4.0-H (``model_type`` granitemoehybrid): Mamba-2 layers with an
attention layer every so often, and in EVERY layer many routed experts beside
a shared MLP.

Source: the model's ``config.json`` (huggingface.co/ibm-granite/
granite-4.0-h-small); what it does not carry follows the one public
implementation of the family (the ``granitemoehybrid`` and ``bamba`` model
code in Hugging Face ``transformers``) and Mamba-2 (arXiv:2405.21060).

``x_0 = embedding_multiplier * E[token]``. Layer ``l``, ``u = RMSNorm(x)``:

- **Mamba-2 mixer** (``layer_types[l] == "mamba"``): ``[z | xBC | dt] = u
  W_in`` (widths ``d_inner | d_inner + 2 d_state | heads``); ``xBC =
  silu(conv(xBC))``, a causal depthwise convolution of ``d_conv`` taps with
  bias; ``[x | B | C] = xBC`` (one group: B and C are shared by all heads);
  ``dt = softplus(dt + dt_bias)`` (no upper clamp), ``A = -exp(A_log)``; the
  state ``S[h]`` (head_dim x d_state, float32) and ``y`` as
  ``ops/ssm2.py`` writes them; gate THEN norm: ``g = y * silu(z)`` in
  float32, ``g * rsqrt(mean(g^2) + eps) * w`` over all ``d_inner`` values;
  ``mixer = g W_out``.
- **attention mixer** (``"attention"``): grouped-query attention with **no
  rotation and no position term of any kind** (``position_embedding_type``
  "nope"), scores scaled by ``attention_multiplier`` (1/128 at the published
  size — not ``head_dim^-1/2``), causal. The paged attention kernels scale by
  ``head_dim^-1/2``, so the queries carry the rest (``attention_multiplier *
  sqrt(head_dim)``, one more rounding of q in the model's dtype).
- ``x += residual_multiplier * mixer``.
- **expert block**, ``v = RMSNorm(x)``: :class:`HeldExpertsLayer` under the
  rule :func:`softmax_of_chosen` (the ``num_experts_per_tok`` largest of the
  router's logits, weights = softmax over those), the experts in
  ``experts_held`` computed here, the shared MLP of width
  ``shared_intermediate_size`` beside them; ``x += residual_multiplier *
  (moe + shared)``.
- ``logits = RMSNorm(x) E^T / logits_scaling`` (tied head). The division is
  applied to the final-normed hidden state the serving methods return (it
  commutes with the product; ``logits_scaling`` 16 is exact in any float
  type), so the engine's tied head needs to know nothing of it.

Serving: ``paged_decode_step``, ``paged_prefill_chunk`` and — both as one
step, so that a tick which carries a prompt chunk reads the weights once —
``paged_decode_chunk_step`` as ``GenerationServer(cache="paged")`` calls
them, built from
:meth:`GraniteMoeHybridForCausalLM.cache_spec`: a Mamba-2 layer owns slot
state (``ssm``: heads x head_dim x d_state float32 — 4 MB a slot a layer at
the published widths — and ``conv``: the last ``d_conv - 1`` inputs of the
convolution), the attention layers blocks of the shared pool. Each step
leaves the per-layer expert loads of its real rows for the caller
(:meth:`GraniteMoeHybridModel.take_step_stats`). ``forward`` is the
cache-free fixture the tests compare with (the sequential recurrence, a full
score matrix).
"""
from __future__ import annotations

import dataclasses
import math
import types
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor
from ..incubate.distributed.models.moe.held_experts import (
    HeldExpertsLayer, _normal, softmax_of_chosen)
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer_base import Layer
from .llama import LlamaMLP, LlamaRMSNorm

__all__ = ["GraniteMoeHybridConfig", "GraniteMoeHybridForCausalLM",
           "granitemoehybrid_tiny_config"]

NEG_INF = -1e30
_ATTN_ROWS = 128       # query rows of one prefill attention call


@dataclasses.dataclass
class GraniteMoeHybridConfig:
    """The published keys under their published names (``intermediate_size``
    is ONE expert's width), ``experts_held`` and ``dtype`` beside them."""
    vocab_size: int = 100352
    hidden_size: int = 4096
    intermediate_size: int = 768
    shared_intermediate_size: int = 1536
    num_hidden_layers: int = 40
    # None: the published period of ten — attention at l % 10 == 5
    layer_types: Optional[Tuple[str, ...]] = None
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    attention_multiplier: float = 0.0078125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 16.0
    mamba_n_heads: int = 128
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 256
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    position_embedding_type: str = "nope"
    num_local_experts: int = 72            # the router's width
    # the half-open range of routed experts whose weights this chip holds
    # (None: all of them)
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 10
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    tie_word_embeddings: bool = True
    dtype: str = "bfloat16"

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = tuple("attention" if i % 10 == 5 else "mamba"
                                     for i in range(L))
        # (a cut in depth keeps the published list and runs its first L)
        self.layer_types = tuple(self.layer_types)[:L]
        if len(self.layer_types) != L or set(self.layer_types) - {
                "mamba", "attention"}:
            raise ValueError(f"layer_types must name {L} layers 'mamba' or "
                             f"'attention', got {self.layer_types}")
        if self.position_embedding_type != "nope":
            raise ValueError("this class encodes no positions "
                             "(position_embedding_type 'nope')")
        if self.mamba_n_groups != 1:
            raise ValueError("one group of B and C is written down here "
                             "(mamba_n_groups 1)")
        if self.mamba_n_heads * self.mamba_d_head \
                != self.mamba_expand * self.hidden_size:
            raise ValueError("mamba_n_heads * mamba_d_head must be "
                             "mamba_expand * hidden_size")
        if self.mamba_proj_bias or self.attention_bias \
                or not self.mamba_conv_bias:
            raise ValueError("projections carry no bias and the convolution "
                             "carries one, as published")
        if not self.tie_word_embeddings:
            raise ValueError("the head is tied to the embedding")
        if self.experts_held is None:
            self.experts_held = (0, self.num_local_experts)
        self.experts_held = tuple(int(e) for e in self.experts_held)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_d_state


def granitemoehybrid_tiny_config(**kw) -> GraniteMoeHybridConfig:
    """Every mechanism at test size, float32: four layers of which the
    second attends, eight experts of which the top three, a d_state of one
    lane tile."""
    return GraniteMoeHybridConfig(**{**dict(
        vocab_size=512, hidden_size=64, intermediate_size=32,
        shared_intermediate_size=48, num_hidden_layers=4,
        layer_types=("mamba", "attention", "mamba", "mamba"),
        num_attention_heads=4, num_key_value_heads=2,
        attention_multiplier=0.125, mamba_n_heads=8, mamba_d_head=16,
        mamba_d_state=128, mamba_chunk_size=16, num_local_experts=8,
        num_experts_per_tok=3, max_position_embeddings=4096,
        dtype="float32"), **kw})


def _val(p):
    return p.value


# ---------------------------------------------------------------------- mixers
class _Mamba2(Layer):
    def __init__(self, cfg: GraniteMoeHybridConfig):
        super().__init__()
        self.cfg = cfg
        H, di, nh, K = (cfg.hidden_size, cfg.d_inner, cfg.mamba_n_heads,
                        cfg.mamba_d_conv)
        init = _normal(cfg.initializer_range)
        self.in_proj = Linear(H, di + cfg.conv_dim + nh, bias_attr=False,
                              weight_attr=init)
        self.conv_weight = self.create_parameter(
            [K, cfg.conv_dim], default_initializer=_normal(0.5))
        self.conv_bias = self.create_parameter([cfg.conv_dim], is_bias=True)
        # how long a head remembers: as the Mamba-2 reference initialises
        # them (normal(0, 0.02) would be no Mamba)
        self.dt_bias = self.create_parameter(
            [nh], default_initializer=lambda s, t: jnp.log(jnp.expm1(jnp.exp(
                jnp.linspace(math.log(1e-3), math.log(1e-1), s[0])))
            ).astype(t))
        self.A_log = self.create_parameter(
            [nh], default_initializer=lambda s, t: jnp.log(
                jnp.linspace(1.0, 16.0, s[0])).astype(t))
        from ..nn.initializer import Constant

        self.D = self.create_parameter([nh],
                                       default_initializer=Constant(1.0))
        self.norm_weight = self.create_parameter(
            [di], default_initializer=Constant(1.0))
        self.out_proj = Linear(di, H, bias_attr=False, weight_attr=init)

    def _split(self, u):
        """u (.., H) -> (z (.., d_inner), xBC (.., conv_dim), dt (..,
        heads))."""
        di, cd = self.cfg.d_inner, self.cfg.conv_dim
        p = u @ _val(self.in_proj.weight)
        return p[..., :di], p[..., di:di + cd], p[..., di + cd:]

    def _operands(self, xc, dt, keep):
        """The convolution's output (T, conv_dim) f32 after silu, the raw dt
        (T, heads) and a (T,) mask of the rows that may move the state ->
        the scan's operands, float32: x (T, heads, head_dim), dt (T, heads)
        — zero where ``keep`` is not —, B, C (T, d_state)."""
        cfg, f = self.cfg, jnp.float32
        di, N = cfg.d_inner, cfg.mamba_d_state
        x = xc[:, :di].reshape(-1, cfg.mamba_n_heads, cfg.mamba_d_head)
        dt = jax.nn.softplus(dt.astype(f) + _val(self.dt_bias).astype(f))
        return (x, jnp.where(keep[:, None], dt, 0.0), xc[:, di:di + N],
                xc[:, di + N:])

    def _A(self):
        return -jnp.exp(_val(self.A_log).astype(jnp.float32))

    def _D(self):
        return _val(self.D).astype(jnp.float32)

    def _finish(self, y, z):
        """Gate, then the norm over all d_inner values, then W_out."""
        f = jnp.float32
        g = y.reshape(z.shape) * jax.nn.silu(z.astype(f))
        g = g * jax.lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                              + self.cfg.rms_norm_eps)
        g = g * _val(self.norm_weight).astype(f)
        return g.astype(z.dtype) @ _val(self.out_proj.weight)

    def _conv(self):
        return _val(self.conv_weight), _val(self.conv_bias)

    def dense(self, u):
        """u (S, H), a whole sequence from a zero state, no cache: the
        sequential recurrence."""
        from ..ops import selective_scan as ss
        from ..ops.ssm2 import ssd_chunk_ref

        cfg = self.cfg
        z, xbc, dt = self._split(u)
        tail = jnp.zeros((cfg.mamba_d_conv - 1, cfg.conv_dim), xbc.dtype)
        xc, _ = ss.causal_conv_chunk(tail, xbc, *self._conv(), 0)
        x, dt, Bm, Cm = self._operands(jax.nn.silu(xc), dt,
                                       jnp.ones((u.shape[0],), bool))
        h0 = jnp.zeros((cfg.mamba_n_heads, cfg.mamba_d_head,
                        cfg.mamba_d_state), jnp.float32)
        y, _ = ssd_chunk_ref(x, dt, self._A(), Bm, Cm, self._D(), h0)
        return self._finish(y, z)

    def _rows(self, xbc, dt, view, act):
        """The decode rows' convolution step and state update from their
        projections xbc (B, conv_dim), dt (B, heads): view = (ssm (B, heads,
        head_dim, d_state) f32, conv tail (B, d_conv - 1, conv_dim)). A row
        that ``act`` (B,) masks keeps both: dt 0, tail kept. Returns (y (B,
        heads, head_dim) f32, new view)."""
        from ..ops import selective_scan as ss
        from ..ops.ssm2 import ssm2_step

        h, tail = view
        xc, new_tail = ss.causal_conv_step(tail, xbc, *self._conv())
        x, dt, Bm, Cm = self._operands(jax.nn.silu(xc), dt, act)
        y, h = ssm2_step(x, dt, self._A(), Bm, Cm, self._D(), h)
        return y, (h, jnp.where(act[:, None, None], new_tail, tail))

    def _chunk_start(self, view, step):
        """What the chunk of ``step`` starts from: its slot's (state, conv
        tail) as ``view`` holds them, zeros on the request's first chunk."""
        h, tail = view
        fresh = step.start == 0
        return (jnp.where(fresh, 0.0, h[step.slot]),
                jnp.where(fresh, 0, tail[step.slot]))

    def _chunk_rows(self, xbc, dt, view, step, start_from):
        """One prompt chunk of the request in ``step.slot`` from its
        projections xbc (C, conv_dim), dt (C, heads), the first
        ``step.n_valid`` rows real: from ``start_from``
        (:meth:`_chunk_start`) to the slot's state after the last real
        token, written into ``view``. Returns (y (C, heads, head_dim) f32,
        new view)."""
        from ..ops import selective_scan as ss
        from ..ops.ssm2 import ssd_chunk

        h0, tail0 = start_from
        n_valid = step.n_valid
        xc, new_tail = ss.causal_conv_chunk(tail0, xbc, *self._conv(),
                                            n_valid)
        x, dt, Bm, Cm = self._operands(jax.nn.silu(xc), dt,
                                       jnp.arange(xbc.shape[0]) < n_valid)
        y, hT = ssd_chunk(x, dt, self._A(), Bm, Cm, self._D(), h0,
                          self.cfg.mamba_chunk_size)

        # (a slice written at the slot, not ``.at[slot].set``: the scatter
        # reads the old row back for its bounds check, in the layout of the
        # scan's product, and behind the joint step's conditional the
        # compiler then lays the WHOLE state out anew for that one row —
        # 403 MB copied a layer at the published widths)
        def put(a, row):
            return jax.lax.dynamic_update_slice_in_dim(a, row[None],
                                                       step.slot, 0)

        h, tail = view
        return y, (put(h, hT), put(tail, new_tail))

    def decode(self, u, view, step):
        """One token a slot: u (B, H), rows masked by ``step.active``."""
        z, xbc, dt = self._split(u)
        y, view = self._rows(xbc, dt, view, step.active)
        return self._finish(y, z), view

    def chunk(self, u, view, step):
        """One prompt chunk: u (C, H)."""
        z, xbc, dt = self._split(u)
        y, view = self._chunk_rows(xbc, dt, view, step,
                                   self._chunk_start(view, step))
        return self._finish(y, z), view

    def joint(self, u, view, step):
        """Both of the above over u (B + C, H), the decode rows first: the
        two projections — all but the whole of the mixer's weights — ONCE
        over the joined rows; the state splits. A call whose decode rows are
        all masked (a tick's second chunk, a chunk that met no decoding row)
        skips their update, which would read and write every slot's state
        to change nothing: ``step.decodes`` is the program's own operand.
        The chunk's start is read BEFORE the rows' update — its slot is no
        decoding row, so the update leaves that row as it is — from the
        array as the program was handed it: read behind the conditional, the
        one row in the layout the scan wants has the compiler lay the whole
        state out anew (tools/compile_check.py holds both)."""
        B = step.active.shape[0]
        z, xbc, dt = self._split(u)
        cfg = self.cfg

        def rows(view):
            return self._rows(xbc[:B], dt[:B], view, step.active)

        def no_rows(view):
            return jnp.zeros((B, cfg.mamba_n_heads, cfg.mamba_d_head),
                             jnp.float32), view

        start_from = self._chunk_start(view, step)
        yd, view = jax.lax.cond(step.decodes, rows, no_rows, view)
        yc, view = self._chunk_rows(xbc[B:], dt[B:], view, step, start_from)
        y = jnp.concatenate([yd.reshape(B, -1), yc.reshape(-1, cfg.d_inner)])
        return self._finish(y, z), view


class _Attention(Layer):
    def __init__(self, cfg: GraniteMoeHybridConfig):
        super().__init__()
        self.cfg = cfg
        H, d = cfg.hidden_size, cfg.head_dim
        init = _normal(cfg.initializer_range)

        def lin(i, o):
            return Linear(i, o, bias_attr=False, weight_attr=init)

        self.q_proj = lin(H, cfg.num_attention_heads * d)
        self.k_proj = lin(H, cfg.num_key_value_heads * d)
        self.v_proj = lin(H, cfg.num_key_value_heads * d)
        self.o_proj = lin(cfg.num_attention_heads * d, H)

    def _qkv(self, u, kernel_scale: bool):
        """u (T, H) -> q (T, heads, d), k, v (T, kv heads, d). With
        ``kernel_scale`` the queries carry ``attention_multiplier *
        sqrt(d)``, for an attention that divides by ``sqrt(d)``."""
        cfg = self.cfg
        T, d = u.shape[0], cfg.head_dim
        q = (u @ _val(self.q_proj.weight)).reshape(T, -1, d)
        if kernel_scale:
            q = (q.astype(jnp.float32)
                 * (cfg.attention_multiplier * math.sqrt(d))).astype(u.dtype)
        return (q, (u @ _val(self.k_proj.weight)).reshape(T, -1, d),
                (u @ _val(self.v_proj.weight)).reshape(T, -1, d))

    def _out(self, o):
        return o.reshape(o.shape[0], -1) @ _val(self.o_proj.weight)

    def dense(self, u):
        """Causal attention over a whole sequence u (S, H), no cache."""
        cfg = self.cfg
        S = u.shape[0]
        q, k, v = self._qkv(u, kernel_scale=False)
        G = cfg.num_key_value_heads
        qg = q.reshape(S, G, -1, cfg.head_dim)
        s = jnp.einsum("sgrd,tgd->grst", qg, k).astype(jnp.float32) \
            * cfg.attention_multiplier
        mask = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
        p = jax.nn.softmax(jnp.where(mask[None, None], s, NEG_INF),
                           -1).astype(u.dtype)
        return self._out(jnp.einsum("grst,tgd->sgrd", p, v).reshape(S, -1))

    def decode(self, u, view, step):
        from ..ops import paged_attention as pa

        q, k, v = self._qkv(u, kernel_scale=True)
        kp, vp = pa.write_decode_kv(*view, k, v, step.tables, step.pos)
        o = pa.paged_decode_attention(q[:, None], kp, vp, step.tables,
                                      step.pos)
        return self._out(o[:, 0]), (kp, vp)

    def chunk(self, u, view, step):
        from ..ops import paged_attention as pa

        q, k, v = self._qkv(u, kernel_scale=True)
        kp, vp = pa.write_chunk_kv(*view, k, v, step.table, step.start)
        return self._out(self._attend_chunk(q, kp, vp, step)), (kp, vp)

    @staticmethod
    def _attend_chunk(q, kp, vp, step):
        """The chunk's queries q (C, heads, d) over its slot's blocks, in
        blocks of _ATTN_ROWS: the attention kernel holds a call's query rows
        (x 4 heads a KV head) whole in VMEM, and a chunk of 256 does not fit
        (refused by the compiler, PR 35)."""
        from ..ops import paged_attention as pa

        return jnp.concatenate([
            pa.paged_prefill_attention(q[None, s:s + _ATTN_ROWS], kp, vp,
                                       step.table, step.start + s)[0]
            for s in range(0, q.shape[0], _ATTN_ROWS)])

    def joint(self, u, view, step):
        """Both of the above over u (B + C, H), the decode rows first: the
        four projections once over the joined rows; BOTH writes, then both
        attentions over the pool as it then stands (no row reads what
        another row of the call writes; with a write between the two reads
        the compiler keeps the old pool for the first and copies it whole:
        PERF.md, PR 32)."""
        from ..ops import paged_attention as pa

        B = step.active.shape[0]
        q, k, v = self._qkv(u, kernel_scale=True)
        kp, vp = pa.write_decode_kv(*view, k[:B], v[:B], step.tables,
                                    step.pos)
        kp, vp = pa.write_chunk_kv(kp, vp, k[B:], v[B:], step.table,
                                   step.start)
        rows = pa.paged_decode_attention(q[:B, None], kp, vp, step.tables,
                                         step.pos)[:, 0]
        return self._out(jnp.concatenate(
            [rows, self._attend_chunk(q[B:], kp, vp, step)])), (kp, vp)


class GraniteMoeHybridLayer(Layer):
    def __init__(self, cfg: GraniteMoeHybridConfig, kind: str):
        super().__init__()
        self.kind, self._res = kind, cfg.residual_multiplier
        self.input_layernorm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.mixer = _Mamba2(cfg) if kind == "mamba" else _Attention(cfg)
        self.post_attention_layernorm = LlamaRMSNorm(cfg.hidden_size,
                                                     cfg.rms_norm_eps)
        shared = LlamaMLP(types.SimpleNamespace(
            hidden_size=cfg.hidden_size,
            intermediate_size=cfg.shared_intermediate_size,
            sequence_parallel=False, context_parallel=False))
        self.mlp = HeldExpertsLayer(
            cfg.hidden_size, cfg.intermediate_size, cfg.num_local_experts,
            cfg.num_experts_per_tok, experts_held=cfg.experts_held,
            shared=shared, rule=softmax_of_chosen, selection_bias=False,
            init_std=cfg.initializer_range, dtype=cfg.dtype)

    def _experts(self, x, valid=None):
        y, counts = self.mlp(self.post_attention_layernorm(Tensor(x)).value,
                             valid)
        return self._mix(x, y), counts

    def _mix(self, x, a):
        """x + residual_multiplier * a, rounded once."""
        return (x.astype(jnp.float32)
                + self._res * a.astype(jnp.float32)).astype(x.dtype)

    def dense(self, x):
        """x (S, H) raw, a whole sequence, no cache."""
        u = self.input_layernorm(Tensor(x)).value
        return self._experts(self._mix(x, self.mixer.dense(u)))[0]

    def serve(self, mode, x, view, step, valid):
        """The rows x (T, H) of one served step in ``mode`` ("decode" |
        "chunk" | "joint": the decode rows, then a chunk). Returns (x, the
        layer's new view, the expert layer's row counts)."""
        u = self.input_layernorm(Tensor(x)).value
        a, view = getattr(self.mixer, mode)(u, view, step)
        x, counts = self._experts(self._mix(x, a), valid)
        return x, view, counts


class GraniteMoeHybridModel(Layer):
    def __init__(self, cfg: GraniteMoeHybridConfig):
        super().__init__()
        self.cfg = cfg
        from ..framework.dtype import convert_dtype

        dtype = None if cfg.dtype == "float32" else convert_dtype(cfg.dtype)

        def cast(layer):
            # one float32 block at a time (see LlamaModel)
            if dtype is not None:
                layer._convert_dtype(dtype)
            return layer

        self.embed_tokens = cast(Embedding(cfg.vocab_size, cfg.hidden_size,
                                           weight_attr=_normal(
                                               cfg.initializer_range)))
        self.layers = LayerList([cast(GraniteMoeHybridLayer(cfg, k))
                                 for k in cfg.layer_types])
        self.norm = cast(LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps))
        self._counts = []

    def _embed(self, ids):
        e = jnp.take(_val(self.embed_tokens.weight), ids, axis=0)
        return (e.astype(jnp.float32)
                * self.cfg.embedding_multiplier).astype(e.dtype)

    def _final(self, x):
        """The final norm, and the logits' divisor ahead of the tied head."""
        h = self.norm(Tensor(x)).value
        return Tensor((h.astype(jnp.float32)
                       / self.cfg.logits_scaling).astype(h.dtype))

    def take_step_stats(self):
        """The expert loads the last step left: (1, layers, held + 1) int32
        — per layer the rows each held expert got and, last, the real rows'
        pairs whose expert lives elsewhere. None if no step ran since the
        last take."""
        counts, self._counts = self._counts, []
        return jnp.stack(counts)[None] if counts else None

    # ----------------------------------------------------------- cache-free
    def forward(self, input_ids):
        ids = input_ids.value if isinstance(input_ids, Tensor) else input_ids
        out = []
        for row in ids:                       # one sequence at a time
            x = self._embed(row)
            for layer in self.layers:
                x = layer.dense(x)
            out.append(x)
        return self._final(jnp.stack(out))

    # -------------------------------------------------------------- serving
    def _serve(self, mode, x, views, step, valid):
        """All layers over the rows x (T, H) of one served step."""
        self._counts, new = [], []
        for layer, view in zip(self.layers, views):
            x, view, counts = layer.serve(
                mode, x, tuple(t.value for t in view), step, valid)
            self._counts.append(counts)
            new.append(tuple(Tensor(t) for t in view))
        return x, new

    def paged_decode_step(self, token, views, block_tables, pos, lora=None,
                          active=None):
        """token (B, 1), one row per slot; ``views[i]``: layer i's cache as
        its spec declared it — ``(ssm state, conv tail)`` slot arrays
        (Mamba-2) or the ``(K, V)`` block pool (attention); ``active`` (B,)
        masks the rows that decode this tick. Returns (final-normed hidden
        over ``logits_scaling`` (B, 1, H), new views)."""
        if lora is not None:
            raise NotImplementedError("no LoRA path for this class")
        B = pos.shape[0]
        act = jnp.ones((B,), bool) if active is None else active > 0
        step = types.SimpleNamespace(tables=block_tables, pos=pos, active=act)
        x, new = self._serve("decode", self._embed(token.value[:, 0]), views,
                             step, act)
        return self._final(x[:, None]), new

    def paged_prefill_chunk(self, input_ids, views, block_table, start,
                            lora=None, last_idx=0, slot=None):
        """One prompt chunk (1, C) of the request in ``slot`` = int32
        ``(slot index, valid tokens in the chunk, 1 on the request's last
        chunk)``. Every layer keeps state, so every layer runs over the
        whole chunk. Returns (final-normed hidden over ``logits_scaling``
        (1, 1, H) of the token at ``last_idx``, new views)."""
        if lora is not None:
            raise NotImplementedError("no LoRA path for this class")
        C = input_ids.shape[1]
        step = types.SimpleNamespace(table=block_table, start=start,
                                     slot=slot[0], n_valid=slot[1])
        x, new = self._serve("chunk", self._embed(input_ids.value[0]), views,
                             step, jnp.arange(C) < slot[1])
        h = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, 0)
        return self._final(h[None]), new

    def paged_decode_chunk_step(self, input_ids, views, block_tables, pos,
                                block_table, start, last_idx, active=None,
                                slot=None):
        """Both of the above as ONE step over B + C rows (input_ids (1, B +
        C), the decode rows first), so that a tick which carries a prompt
        chunk reads every weight — the held experts' 0.68 GB a layer, the
        mixers' projections — once. Only what differs by kind of row splits:
        the convolution, the state's update and scan, the attention. Returns
        (final-normed hidden over ``logits_scaling`` (1, B + 1, H): the
        decode rows, then the chunk's token at ``last_idx``; new views)."""
        B = block_tables.shape[0]
        C = input_ids.shape[1] - B
        act = jnp.ones((B,), bool) if active is None else active > 0
        step = types.SimpleNamespace(
            tables=block_tables, pos=pos, active=act, decodes=jnp.any(act),
            table=block_table, start=start, slot=slot[0], n_valid=slot[1])
        valid = jnp.concatenate([act, jnp.arange(C) < slot[1]])
        x, new = self._serve("joint", self._embed(input_ids.value[0]), views,
                             step, valid)
        h = jnp.concatenate(
            [x[:B], jax.lax.dynamic_slice_in_dim(x, B + last_idx, 1, 0)])
        return self._final(h[None]), new


class GraniteMoeHybridForCausalLM(Layer):
    def __init__(self, cfg: GraniteMoeHybridConfig):
        super().__init__()
        self.cfg = cfg
        self.model = GraniteMoeHybridModel(cfg)

    def cache_spec(self):
        """The per-layer cache declaration the serving engine is built from
        (inference/cache_spec.py)."""
        from ..framework.dtype import convert_dtype
        from ..inference import cache_spec as cs

        cfg = self.cfg
        dtype = jnp.zeros((), convert_dtype(cfg.dtype)).dtype
        per = {"mamba": cs.state([
            ("ssm", (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
             np.float32),
            ("conv", (cfg.mamba_d_conv - 1, cfg.conv_dim), dtype)]),
            "attention": cs.full(cfg.num_key_value_heads, cfg.head_dim)}
        return cs.CacheSpec([per[k] for k in cfg.layer_types], dtype)

    def logits(self, h):
        return h @ _val(self.model.embed_tokens.weight).T

    def forward(self, input_ids):
        """Logits (B, S, V) of whole sequences, no cache."""
        return Tensor(self.logits(self.model(input_ids).value))
