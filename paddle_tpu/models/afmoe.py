"""Trinity (``model_type`` afmoe): window and full attention layers, gated
and QK-normed, and after a few dense layers many sigmoid-routed experts
beside a shared one.

Source: the model's ``config.json`` (huggingface.co/arcee-ai/Trinity-Mini);
what it does not carry follows the one public implementation of the family
(the ``afmoe`` model code in Hugging Face ``transformers``) and the model
card.

``x_0 = sqrt(hidden) * E[token]`` (``mup_enabled``; the scale applies to the
input only). Layer ``l`` of kind ``layer_types[l]``, ``u = RMSNorm(x)``:

- ``q = u W_q``, ``k = u W_k``, ``v = u W_v`` (heads of ``head_dim``), ``g =
  u W_g`` (as wide as q); no biases. ``q``, ``k`` normed per head by RMSNorms
  of ``head_dim`` weights each.
- **window layers** (``sliding_attention``): q and k rotated by rope
  (``rope_theta``, the half-split rotation over the whole head) at the
  absolute position; a query at ``i`` sees keys ``j`` with ``i - j <
  sliding_window``. **full layers**: no rotation and no position term of
  any kind. Scores ``q.k / sqrt(head_dim)``, causal, softmax in float32.
- ``o = (softmax v) * sigmoid(g)``, then ``a = o W_o``; ``x += RMSNorm(a)``.
- ``w = RMSNorm(x)``; layers ``l < num_dense_layers`` a SwiGLU of width
  ``intermediate_size`` (``models/llama.py``'s MLP), every later layer
  :class:`HeldExpertsLayer` under :func:`deepseek_v3_rule` (sigmoid scores of
  the router's float32 logits, the ``num_experts_per_tok`` of score +
  selection bias chosen, weights the chosen scores over their sum times
  ``route_scale``) with the experts in ``experts_held`` computed here and a
  shared SwiGLU of width ``moe_intermediate_size`` beside them; ``x +=
  RMSNorm(f)``.
- ``logits = RMSNorm(x) W_head`` (untied).

Serving: ``paged_decode_step``, ``paged_prefill_chunk`` and — both as one
step, so that a tick which carries a prompt chunk reads the weights once —
``paged_decode_chunk_step`` as ``GenerationServer(cache="paged")`` calls
them, built from :meth:`AfmoeForCausalLM.cache_spec`: a window layer owns a
ring of blocks per slot (``window``), a full layer blocks of the shared pool
(``full``), both head-major (4 KV heads are no bfloat16 sublane multiple: a
token-major block would be padded to 16 of them in HBM). Each step leaves
the per-layer expert loads of its real rows for the caller
(:meth:`AfmoeModel.take_step_stats`). ``forward`` is the cache-free fixture
the tests compare with (a full score matrix).
"""
from __future__ import annotations

import dataclasses
import types
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..framework.core import Tensor
from ..incubate.distributed.models.moe.held_experts import (
    HeldExpertsLayer, _normal, deepseek_v3_rule)
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer_base import Layer
from ..ops import paged_attention as pa
from .llama import LlamaMLP, LlamaRMSNorm, _rope_rotate

__all__ = ["AfmoeConfig", "AfmoeForCausalLM", "afmoe_tiny_config"]

_ATTN_ROWS = 128       # query rows of one prefill attention call
KINDS = {"sliding_attention": "window", "full_attention": "full"}


@dataclasses.dataclass
class AfmoeConfig:
    """The published keys under their published names
    (``intermediate_size`` is the dense layers' width,
    ``moe_intermediate_size`` one expert's), ``experts_held`` and ``dtype``
    beside them."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    # None: full attention at every ``global_attn_every_n_layers``-th layer
    layer_types: Optional[Tuple[str, ...]] = None
    global_attn_every_n_layers: int = 4
    sliding_window: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10000.0
    num_experts: int = 128                # the router's width
    # the half-open range of routed experts whose weights this chip holds
    # (None: all of them)
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    n_group: int = 1
    topk_group: int = 1
    mup_enabled: bool = True
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        L = self.num_hidden_layers
        if self.layer_types is None:
            n = self.global_attn_every_n_layers
            self.layer_types = tuple(
                "full_attention" if (i + 1) % n == 0 else "sliding_attention"
                for i in range(L))
        # (a cut in depth keeps the published list and runs its first L)
        self.layer_types = tuple(self.layer_types)[:L]
        if len(self.layer_types) != L or set(self.layer_types) - set(KINDS):
            raise ValueError(f"layer_types must name {L} layers "
                             f"{sorted(KINDS)}, got {self.layer_types}")
        if self.score_func != "sigmoid":
            raise ValueError("the router written down here scores by sigmoid "
                             "(score_func 'sigmoid')")
        if self.n_group != 1 or self.topk_group != 1:
            raise ValueError("experts are chosen with no group limit "
                             "(n_group = topk_group = 1)")
        if self.tie_word_embeddings:
            raise ValueError("the head is untied from the embedding")
        if self.head_dim % 2:
            raise ValueError("rope rotates pairs: head_dim is even")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        self.experts_held = tuple(int(e) for e in self.experts_held)

    def kind(self, layer: int) -> str:
        """"window" or "full"."""
        return KINDS[self.layer_types[layer]]


def afmoe_tiny_config(**kw) -> AfmoeConfig:
    """Every mechanism at test size, float32: two periods of S S S F, the
    first two layers dense, a window of 24, sixteen experts of which the top
    four, experts 0-7 held here."""
    return AfmoeConfig(**{**dict(
        vocab_size=256, hidden_size=64, intermediate_size=96,
        moe_intermediate_size=32, num_hidden_layers=8, num_dense_layers=2,
        sliding_window=24, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_experts=16, experts_held=(0, 8),
        num_experts_per_tok=4, max_position_embeddings=4096,
        dtype="float32"), **kw})


def _val(p):
    return p.value


def _rope(x, pos, theta: float):
    """x (T, heads, d) at int positions ``pos`` (T,): the half-split
    rotation (``models/llama.py``) at frequencies ``theta^(-2j/d)``."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    return _rope_rotate(x, jnp.cos(ang)[:, None], jnp.sin(ang)[:, None])


def _mlp(cfg: AfmoeConfig, width: int) -> LlamaMLP:
    return LlamaMLP(types.SimpleNamespace(
        hidden_size=cfg.hidden_size, intermediate_size=width,
        sequence_parallel=False, context_parallel=False))


class AfmoeAttention(Layer):
    def __init__(self, cfg: AfmoeConfig, kind: str):
        super().__init__()
        self.cfg, self.kind = cfg, kind
        self.window = cfg.sliding_window if kind == "window" else 0
        self.scope = f"{kind}_attend"
        H, d = cfg.hidden_size, cfg.head_dim
        nq, nkv = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
        init = _normal(cfg.initializer_range)

        def lin(i, o):
            return Linear(i, o, bias_attr=False, weight_attr=init)

        self.q_proj, self.k_proj, self.v_proj = lin(H, nq), lin(H, nkv), \
            lin(H, nkv)
        self.gate_proj, self.o_proj = lin(H, nq), lin(nq, H)
        self.q_norm = LlamaRMSNorm(d, cfg.rms_norm_eps)
        self.k_norm = LlamaRMSNorm(d, cfg.rms_norm_eps)

    def _project(self, u, pos):
        """u (T, H) at positions ``pos`` (T,) -> q (T, heads, d), k, v (T,
        kv heads, d), the gate's input g (T, heads * d): normed, and
        rotated in a window layer."""
        cfg = self.cfg
        T, d = u.shape[0], cfg.head_dim
        q = self.q_norm(Tensor((u @ _val(self.q_proj.weight)).reshape(
            T, -1, d))).value
        k = self.k_norm(Tensor((u @ _val(self.k_proj.weight)).reshape(
            T, -1, d))).value
        if self.window:
            q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
        return (q, k, (u @ _val(self.v_proj.weight)).reshape(T, -1, d),
                u @ _val(self.gate_proj.weight))

    def _out(self, o, g):
        """The heads' outputs o (T, heads, d) gated by sigmoid(g), rounded
        once, then W_o."""
        f = jnp.float32
        o = (o.reshape(g.shape).astype(f) * jax.nn.sigmoid(g.astype(f)))
        return o.astype(g.dtype) @ _val(self.o_proj.weight)

    def dense(self, u):
        """Attention over a whole sequence u (S, H), no cache."""
        S = u.shape[0]
        pos = jnp.arange(S)
        q, k, v, g = self._project(u, pos)
        mask = pos[None, :] <= pos[:, None]
        if self.window:
            mask = mask & (pos[None, :] > pos[:, None] - self.window)
        o = pa.masked_attention(q[None], k[None], v[None], mask[None])[0]
        return self._out(o, g)

    # ---------------------------------------------------------- served rows
    def _rows(self, q, k, v, view, step):
        """The decode rows: their K/V written, then their attention over the
        cache as it then stands. Returns (out (B, heads, d), new view)."""
        kp, vp = view
        if self.window:
            kp, vp = pa.write_ring_kv(kp, vp, k, v, step.rings, step.pos,
                                      head_major=True)
            with jax.named_scope(self.scope):
                o = pa.paged_window_attention(q[:, None], kp, vp, step.rings,
                                              step.pos, self.window,
                                              head_major=True)
        else:
            kp, vp = pa.write_decode_kv(kp, vp, k, v, step.tables, step.pos,
                                        head_major=True)
            with jax.named_scope(self.scope):
                o = pa.paged_decode_attention(q[:, None], kp, vp,
                                              step.tables, step.pos,
                                              head_major=True)
        return o[:, 0], (kp, vp)

    def _chunk_window(self, q, k, v, view, step):
        """A chunk of a window layer: the ring's past beside the chunk, then
        the ring's overwrite (``ops/paged_attention.py``)."""
        with jax.named_scope(self.scope):
            o, kp, vp = pa.window_chunk_attention(
                q[None], k, v, *view, step.ring_row, step.start, step.n_valid,
                self.window, head_major=True)
        return o[0], (kp, vp)

    def _chunk_full(self, q, kp, vp, step):
        with jax.named_scope(self.scope):
            return pa.paged_prefill_attention_in_rows(
                q, kp, vp, step.table, step.start, _ATTN_ROWS,
                head_major=True)

    def decode(self, u, view, step):
        """One token a slot: u (B, H)."""
        q, k, v, g = self._project(u, step.pos)
        o, view = self._rows(q, k, v, view, step)
        return self._out(o, g), view

    def chunk(self, u, view, step):
        """One prompt chunk: u (C, H)."""
        q, k, v, g = self._project(u, step.start + jnp.arange(u.shape[0]))
        if self.window:
            o, view = self._chunk_window(q, k, v, view, step)
        else:
            kp, vp = pa.write_chunk_kv(*view, k, v, step.table, step.start,
                                       head_major=True)
            o, view = self._chunk_full(q, kp, vp, step), (kp, vp)
        return self._out(o, g), view

    def joint(self, u, view, step):
        """Both of the above over u (B + C, H), the decode rows first: the
        five projections once over the joined rows. A window layer's chunk
        reads the ring's past before anything writes; then every write, then
        the attentions over the cache as it then stands (no row reads what
        another row of the call writes; with a write between two reads of a
        pool the compiler keeps the old pool for the first and copies it
        whole: PERF.md section 5)."""
        B = step.active.shape[0]
        C = u.shape[0] - B
        q, k, v, g = self._project(u, jnp.concatenate(
            [step.pos, step.start + jnp.arange(C)]))
        if self.window:
            oc, view = self._chunk_window(q[B:], k[B:], v[B:], view, step)
            od, view = self._rows(q[:B], k[:B], v[:B], view, step)
        else:
            kp, vp = pa.write_chunk_kv(*view, k[B:], v[B:], step.table,
                                       step.start, head_major=True)
            od, (kp, vp) = self._rows(q[:B], k[:B], v[:B], (kp, vp), step)
            oc, view = self._chunk_full(q[B:], kp, vp, step), (kp, vp)
        return self._out(jnp.concatenate([od, oc]), g), view


class AfmoeDecoderLayer(Layer):
    def __init__(self, cfg: AfmoeConfig, idx: int):
        super().__init__()
        H, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.kind = cfg.kind(idx)
        self.input_layernorm = LlamaRMSNorm(H, eps)
        self.self_attn = AfmoeAttention(cfg, self.kind)
        self.post_attention_layernorm = LlamaRMSNorm(H, eps)
        self.pre_mlp_layernorm = LlamaRMSNorm(H, eps)
        self.post_mlp_layernorm = LlamaRMSNorm(H, eps)
        self.dense_mlp = idx < cfg.num_dense_layers
        if self.dense_mlp:
            self.mlp = _mlp(cfg, cfg.intermediate_size)
        else:
            F = cfg.moe_intermediate_size
            shared = (_mlp(cfg, F * cfg.num_shared_experts)
                      if cfg.num_shared_experts else None)
            self.mlp = HeldExpertsLayer(
                H, F, cfg.num_experts, cfg.num_experts_per_tok,
                experts_held=cfg.experts_held, shared=shared,
                rule=deepseek_v3_rule(cfg.route_norm, cfg.route_scale),
                selection_bias=True, init_std=cfg.initializer_range,
                dtype=cfg.dtype)

    def _add_normed(self, x, norm, a):
        return x + norm(Tensor(a)).value

    def _ffn(self, x, valid=None):
        """x + RMSNorm(FFN(RMSNorm(x))), and the expert layer's row counts
        (None for a dense layer)."""
        w = self.pre_mlp_layernorm(Tensor(x)).value
        if self.dense_mlp:
            f, counts = self.mlp(Tensor(w)).value, None
        else:
            f, counts = self.mlp(w, valid)
        return self._add_normed(x, self.post_mlp_layernorm, f), counts

    def dense(self, x):
        """x (S, H) raw, a whole sequence, no cache."""
        a = self.self_attn.dense(self.input_layernorm(Tensor(x)).value)
        return self._ffn(self._add_normed(x, self.post_attention_layernorm,
                                          a))[0]

    def serve(self, mode, x, view, step, valid):
        """The rows x (T, H) of one served step in ``mode`` ("decode" |
        "chunk" | "joint": the decode rows, then a chunk). Returns (x, the
        layer's new view, the expert layer's row counts or None)."""
        u = self.input_layernorm(Tensor(x)).value
        a, view = getattr(self.self_attn, mode)(u, view, step)
        x, counts = self._ffn(
            self._add_normed(x, self.post_attention_layernorm, a), valid)
        return x, view, counts


class AfmoeModel(Layer):
    def __init__(self, cfg: AfmoeConfig):
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
        self.layers = LayerList([cast(AfmoeDecoderLayer(cfg, i))
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = cast(LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps))
        self._counts = []

    def _embed(self, ids):
        e = jnp.take(_val(self.embed_tokens.weight), ids, axis=0)
        if not self.cfg.mup_enabled:
            return e
        return (e.astype(jnp.float32)
                * self.cfg.hidden_size ** 0.5).astype(e.dtype)

    def take_step_stats(self):
        """The expert loads the last step left: (1, expert layers, held + 1)
        int32 — per expert layer the rows each held expert got and, last,
        the real rows' pairs whose expert lives elsewhere. None if no step
        ran since the last take."""
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
        return self.norm(Tensor(jnp.stack(out)))

    # -------------------------------------------------------------- serving
    def _ring_blocks(self, views) -> int:
        """Blocks of a window layer's ring, from its pool's block size (the
        window plus the block being written; 0 without a window layer).
        Blocks are head-major: (kv heads, block size, head_dim)."""
        for layer, view in zip(self.layers, views):
            if layer.kind == "window":
                return -(-self.cfg.sliding_window // view[0].shape[2]) + 1
        return 0

    def _step(self, views, **kw):
        """What a served step knows of its rows, for every layer alike:
        ``rings`` (B, R) of the decode rows (scratch for a masked row),
        ``ring_row`` (R,) of the chunk's slot."""
        step = types.SimpleNamespace(**kw)
        R = self._ring_blocks(views)
        if R and "active" in kw:
            step.rings = jnp.where(
                kw["active"][:, None],
                pa.slot_ring_tables(jnp.arange(kw["pos"].shape[0]), R), 0)
        if R and "slot" in kw:
            step.ring_row = pa.slot_ring_tables(kw["slot"][None], R)[0]
        return step

    def _serve(self, mode, x, views, step, valid):
        """All layers over the rows x (T, H) of one served step."""
        self._counts, new = [], []
        for layer, view in zip(self.layers, views):
            x, view, counts = layer.serve(
                mode, x, tuple(t.value for t in view), step, valid)
            if counts is not None:
                self._counts.append(counts)
            new.append(tuple(Tensor(t) for t in view))
        return x, new

    def paged_decode_step(self, token, views, block_tables, pos, lora=None,
                          active=None):
        """token (B, 1), one row per slot; ``views[i]``: layer i's cache as
        its spec declared it — the ``(K, V)`` ring pool (window) or block
        pool (full); ``active`` (B,) masks the rows that decode this tick.
        Returns (final-normed hidden (B, 1, H), new views)."""
        if lora is not None:
            raise NotImplementedError("no LoRA path for this class")
        B = pos.shape[0]
        act = jnp.ones((B,), bool) if active is None else active > 0
        vals = [tuple(t.value for t in v) for v in views]
        step = self._step(vals, tables=block_tables, pos=pos, active=act)
        x, new = self._serve("decode", self._embed(token.value[:, 0]), views,
                             step, act)
        return self.norm(Tensor(x[:, None])), new

    def paged_prefill_chunk(self, input_ids, views, block_table, start,
                            lora=None, last_idx=0, slot=None):
        """One prompt chunk (1, C) of the request in ``slot`` = int32
        ``(slot index, valid tokens in the chunk, 1 on the request's last
        chunk)`` at positions ``start + arange(C)``. Returns (final-normed
        hidden (1, 1, H) of the token at ``last_idx``, new views)."""
        if lora is not None:
            raise NotImplementedError("no LoRA path for this class")
        C = input_ids.shape[1]
        vals = [tuple(t.value for t in v) for v in views]
        step = self._step(vals, table=block_table, start=start, slot=slot[0],
                          n_valid=slot[1])
        x, new = self._serve("chunk", self._embed(input_ids.value[0]), views,
                             step, jnp.arange(C) < slot[1])
        h = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, 0)
        return self.norm(Tensor(h[None])), new

    def paged_decode_chunk_step(self, input_ids, views, block_tables, pos,
                                block_table, start, last_idx, active=None,
                                slot=None):
        """Both of the above as ONE step over B + C rows (input_ids (1, B +
        C), the decode rows first), so that a tick which carries a prompt
        chunk reads every weight — the held experts' 0.4 GB a layer, the
        attention's five matrices — once. Only the attention splits.
        Returns (final-normed hidden (1, B + 1, H): the decode rows, then the
        chunk's token at ``last_idx``; new views)."""
        B = block_tables.shape[0]
        C = input_ids.shape[1] - B
        act = jnp.ones((B,), bool) if active is None else active > 0
        vals = [tuple(t.value for t in v) for v in views]
        step = self._step(vals, tables=block_tables, pos=pos, active=act,
                          table=block_table, start=start, slot=slot[0],
                          n_valid=slot[1])
        valid = jnp.concatenate([act, jnp.arange(C) < slot[1]])
        x, new = self._serve("joint", self._embed(input_ids.value[0]), views,
                             step, valid)
        h = jnp.concatenate(
            [x[:B], jax.lax.dynamic_slice_in_dim(x, B + last_idx, 1, 0)])
        return self.norm(Tensor(h[None])), new


class AfmoeForCausalLM(Layer):
    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.cfg = cfg
        self.model = AfmoeModel(cfg)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size, bias_attr=False,
                              weight_attr=_normal(cfg.initializer_range))
        if cfg.dtype != "float32":
            from ..framework.dtype import convert_dtype

            self.lm_head._convert_dtype(convert_dtype(cfg.dtype))

    def cache_spec(self):
        """The per-layer cache declaration the serving engine is built from
        (inference/cache_spec.py): a ring of the window per slot for a
        window layer, blocks of the shared pool for a full one."""
        from ..framework.dtype import convert_dtype
        from ..inference import cache_spec as cs

        cfg = self.cfg
        dtype = jnp.zeros((), convert_dtype(cfg.dtype)).dtype
        kv, d = cfg.num_key_value_heads, cfg.head_dim
        per = {"window": cs.window(cfg.sliding_window, kv, d, layout="head"),
               "full": cs.full(kv, d, layout="head")}
        return cs.CacheSpec([per[cfg.kind(i)]
                             for i in range(cfg.num_hidden_layers)], dtype)

    def forward(self, input_ids):
        """Logits (B, S, V) of whole sequences, no cache."""
        return self.lm_head(self.model(input_ids))
