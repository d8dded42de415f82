"""Mistral-Small-4 (``model_type`` mistral4): the language model — latent
attention (MLA) and, in every layer, many routed experts beside a shared one.

Source: the model's ``config.json``
(huggingface.co/mistralai/Mistral-Small-4-119B-2603). The layer follows the
one public implementation that reads exactly this set of keys, DeepSeek-V3's
(arXiv:2412.19437); the position-dependent query scale is Llama 4's, under
the key Mistral's configs carry it by (``llama_4_scaling_beta``). The vision
tower has no sizes in the published language config and is not here.

For a token's hidden state ``x`` at position ``t``, ``h = RMSNorm(x)``:

- queries ``c_q = RMSNorm(W_dq h)``; ``q = W_uq c_q`` -> heads x ``[q_nope |
  q_rope]``;
- the latent row ``[c | k_r] = W_dkv h``; ``c = RMSNorm(c)``; ``k_r =
  RoPE_t(k_r)``: one rope key a token, shared by all heads. **The cache holds
  ``[c | k_r]``, after the norm and after the rotation** — one row of
  ``kv_lora_rank + qk_rope_head_dim`` values a token a layer (``cache_spec``:
  every layer ``latent``);
- per head ``[k_nope_i | v_i] = W_ukv,i c`` (the EXPANDED form,
  :meth:`Mistral4Attention.dense` with ``absorbed=False``); served in the
  ABSORBED form: ``q~_i = W_uk,i^T q_nope,i``, scores ``(q~_i . c_u +
  q_rope,i . k_r,u) * sigma * a_t``, ``o_i = W_uv,i sum_u p_i(t, u) c_u``
  (``ops/latent_attention.py``), so that the cache row is the key of every
  head and its latent part the value;
- rotation of interleaved pairs (2j, 2j+1) at YaRN's frequencies
  (:func:`yarn_inv_freq`). The pairs are first brought side by side (even
  lanes, then odd lanes — the same permutation in ``q_rope`` and ``k_r``, so
  no score changes) and then rotated by ``models/llama.py``'s half-split
  rotation; cos and sin carry no factor (``mscale`` = ``mscale_all_dim``);
- ``sigma = (nope + rope)^-1/2 * m^2``, ``m = 0.1 * mscale_all_dim *
  ln(factor) + 1``; ``a_t = 1 + beta * ln(1 + floor(t / original_max))``;
- the expert layer is :class:`HeldExpertsLayer` (incubate/distributed/
  models/moe/held_experts.py): the router over ``n_routed_experts``, the
  experts in ``experts_held`` computed here, the shared expert (``models/
  llama.py``'s SwiGLU MLP) beside them.

Serving: ``paged_decode_step``, ``paged_prefill_chunk`` and
``paged_decode_chunk_step`` as ``GenerationServer(cache="paged")`` calls
them. Each step leaves the per-layer expert loads of its real rows for the
caller (:meth:`Mistral4Model.take_step_stats`); which rows are real it reads
from what the engine passes anyway — a decode row parked on the scratch
block has a zeroed table row, a chunk's rows after ``last_idx`` are padding.
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
    HeldExpertsLayer, _normal, deepseek_v3_rule)
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..nn.layer_base import Layer
from .llama import LlamaMLP, LlamaRMSNorm, _rope_rotate

__all__ = ["Mistral4Config", "Mistral4ForCausalLM", "mistral4_tiny_config",
           "yarn_inv_freq"]


@dataclasses.dataclass
class Mistral4Config:
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 36
    num_attention_heads: int = 32
    q_lora_rank: int = 1024
    kv_lora_rank: int = 256
    qk_nope_head_dim: int = 64
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 128          # the router's width
    # the half-open range of routed experts whose weights this chip holds
    # (None: all of them)
    experts_held: Optional[Tuple[int, int]] = None
    num_experts_per_tok: int = 4
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rms_norm_eps: float = 1e-6
    max_position_embeddings: int = 1048576
    rope_theta: float = 10000.0
    rope_factor: float = 128.0
    rope_original_max_position_embeddings: int = 8192
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale_all_dim: float = 1.0
    llama_4_scaling_beta: float = 0.1
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.tie_word_embeddings:
            raise ValueError("Mistral-Small-4 does not tie its head")
        if self.qk_rope_head_dim % 2:
            raise ValueError("rope rotates pairs: qk_rope_head_dim is even")
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        self.experts_held = tuple(int(e) for e in self.experts_held)

    @property
    def latent_width(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        m = (0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
             if self.rope_factor > 1 and self.rope_mscale_all_dim else 1.0)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m


def mistral4_tiny_config(**kw) -> Mistral4Config:
    """Every mechanism at test size, float32: three layers, eight experts of
    which the top two, a YaRN original length short enough that both the
    frequency ramp and a query scale other than 1 are reached within a few
    dozen positions."""
    return Mistral4Config(**{**dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, q_lora_rank=32, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=16,
        moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2,
        max_position_embeddings=4096, rope_factor=4.0,
        rope_original_max_position_embeddings=16, dtype="float32"), **kw})


def yarn_inv_freq(cfg: Mistral4Config) -> np.ndarray:
    """YaRN's frequency of each rope pair: the plain one where the pair
    turns more than ``beta_fast`` times within the original length, the plain
    one over ``factor`` where it turns fewer than ``beta_slow`` times, a
    linear ramp between."""
    dr, theta = cfg.qk_rope_head_dim, cfg.rope_theta
    orig = cfg.rope_original_max_position_embeddings
    j = np.arange(dr // 2, dtype=np.float64)
    f = theta ** (-2.0 * j / dr)

    def corr(turns):
        return dr * math.log(orig / (2 * math.pi * turns)) \
            / (2 * math.log(theta))

    lo = max(math.floor(corr(cfg.rope_beta_fast)), 0)
    hi = min(math.ceil(corr(cfg.rope_beta_slow)), dr - 1)
    if hi == lo:
        hi += 0.001
    r = np.clip((j - lo) / (hi - lo), 0.0, 1.0)
    return (f * (1 - r) + f / cfg.rope_factor * r).astype(np.float32)


def _val(p):
    return p.value


def _rope(x, pos, inv_freq):
    """x (T, ..., dr) with interleaved pairs, at int positions ``pos``
    (T,): the pairs brought side by side (even lanes | odd lanes), then
    rotated."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (-1,)
    side = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    return _rope_rotate(side, jnp.cos(ang).reshape(shape),
                        jnp.sin(ang).reshape(shape))


class Mistral4Attention(Layer):
    def __init__(self, cfg: Mistral4Config):
        super().__init__()
        self.cfg = cfg
        init = _normal(cfg.initializer_range)
        H, nh = cfg.hidden_size, cfg.num_attention_heads
        dqk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim

        def lin(i, o):
            return Linear(i, o, bias_attr=False, weight_attr=init)

        self.q_a_proj = lin(H, cfg.q_lora_rank)
        self.q_a_layernorm = LlamaRMSNorm(cfg.q_lora_rank, cfg.rms_norm_eps)
        self.q_b_proj = lin(cfg.q_lora_rank, nh * dqk)
        self.kv_a_proj = lin(H, cfg.latent_width)
        self.kv_a_layernorm = LlamaRMSNorm(cfg.kv_lora_rank, cfg.rms_norm_eps)
        self.kv_b_proj = lin(cfg.kv_lora_rank,
                             nh * (cfg.qk_nope_head_dim + cfg.v_head_dim))
        self.o_proj = lin(nh * cfg.v_head_dim, H)
        self._inv_freq = jnp.asarray(yarn_inv_freq(cfg))

    # ------------------------------------------------------------ the parts
    def _project(self, h, pos):
        """h (T, H) at positions ``pos`` (T,) -> q_nope (T, heads, nope),
        rotated q_rope (T, heads, rope), the cache rows (T, latent_width) =
        [normed c | rotated k_r]."""
        cfg = self.cfg
        T, nh, dn = h.shape[0], cfg.num_attention_heads, cfg.qk_nope_head_dim
        cq = self.q_a_layernorm(Tensor(h @ _val(self.q_a_proj.weight))).value
        q = (cq @ _val(self.q_b_proj.weight)).reshape(T, nh, -1)
        ckr = h @ _val(self.kv_a_proj.weight)
        c = self.kv_a_layernorm(Tensor(ckr[:, :cfg.kv_lora_rank])).value
        k_r = _rope(ckr[:, cfg.kv_lora_rank:], pos, self._inv_freq)
        return (q[..., :dn], _rope(q[..., dn:], pos, self._inv_freq),
                jnp.concatenate([c, k_r], axis=-1))

    def _w_ukv(self):
        cfg = self.cfg
        w = _val(self.kv_b_proj.weight).reshape(
            cfg.kv_lora_rank, cfg.num_attention_heads, -1)
        return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]

    def _query_rows(self, q_nope, q_rope, width):
        """The absorbed queries laid out like a cache row: [W_uk^T q_nope |
        q_rope | 0] (T, heads, width)."""
        w_uk, _ = self._w_ukv()
        qt = jnp.einsum("thd,rhd->thr", q_nope, w_uk).astype(q_nope.dtype)
        pad = width - qt.shape[-1] - q_rope.shape[-1]
        return jnp.pad(jnp.concatenate([qt, q_rope], axis=-1),
                       ((0, 0), (0, 0), (0, pad)))

    def _out(self, o_lat):
        """o_lat (T, heads, kv_lora_rank), the probabilities' sum over the
        latents -> the layer's output (T, H)."""
        _, w_uv = self._w_ukv()
        v = jnp.einsum("thr,rhd->thd", o_lat, w_uv).astype(o_lat.dtype)
        return v.reshape(v.shape[0], -1) @ _val(self.o_proj.weight)

    def _qscale(self):
        return (self.cfg.llama_4_scaling_beta,
                self.cfg.rope_original_max_position_embeddings)

    # ------------------------------------------------------------ cache-free
    def dense(self, h, absorbed: bool = True):
        """Causal attention over a whole sequence h (S, H) at positions
        0..S-1, no cache: in the absorbed form the served path computes, or
        in the expanded form of the published description."""
        from ..ops.latent_attention import NEG_INF, query_scale

        cfg = self.cfg
        S = h.shape[0]
        pos = jnp.arange(S)
        q_nope, q_rope, rows = self._project(h, pos)
        c, k_r = rows[:, :cfg.kv_lora_rank], rows[:, cfg.kv_lora_rank:]
        scale = cfg.softmax_scale * query_scale(pos, self._qscale())
        mask = pos[None, :] <= pos[:, None]
        w_uk, w_uv = self._w_ukv()
        if absorbed:
            qr = self._query_rows(q_nope, q_rope, rows.shape[-1])
            s = jnp.einsum("shd,td->hst", qr, rows)
        else:
            k_nope = jnp.einsum("tr,rhd->thd", c, w_uk)
            s = (jnp.einsum("shd,thd->hst", q_nope, k_nope)
                 + jnp.einsum("shd,td->hst", q_rope, k_r))
        s = s.astype(jnp.float32) * scale[None, :, None]
        p = jax.nn.softmax(jnp.where(mask[None], s, NEG_INF), -1).astype(
            h.dtype)
        if absorbed:
            return self._out(jnp.einsum("hst,tr->shr", p, c))
        v = jnp.einsum("tr,rhd->thd", c, w_uv)
        o = jnp.einsum("hst,thd->shd", p, v)
        return o.reshape(S, -1) @ _val(self.o_proj.weight)

    # --------------------------------------------------------------- serving
    def paged(self, h, pool, step):
        """The served attention of h (T, H): the new cache rows go into the
        pool (``step.write``), then all T tokens attend over the pool as it
        then stands (``step.attend``; :class:`_Paged`). Returns (out (T, H),
        new pool)."""
        q_nope, q_rope, rows = self._project(h, step.pos)
        width = pool.shape[-1]
        pool = step.write(pool, jnp.pad(
            rows, ((0, 0), (0, width - rows.shape[-1]))))
        o_lat = step.attend(self._query_rows(q_nope, q_rope, width), pool)
        return self._out(o_lat), pool


class _Paged:
    """What one served step knows of its rows, for every layer alike: where
    the new cache rows go, and how the rows attend. ``tables`` (B, M) and
    ``pos`` (B,) of B decode rows, then ``table`` (M,) and ``start`` of one
    prompt chunk of C tokens; either may be absent."""

    def __init__(self, cfg, tables=None, pos=None, table=None, start=None,
                 C=0):
        self.cfg, self.tables, self.dpos = cfg, tables, pos
        self.table, self.start, self.C = table, start, C
        self.B = 0 if tables is None else tables.shape[0]
        parts = []
        if self.B:
            parts.append(pos)
        if C:
            parts.append(start + jnp.arange(C, dtype=jnp.int32))
        self.pos = jnp.concatenate(parts)           # of all T rows

    def write(self, pool, rows):
        """Both writes first, then (in :meth:`attend`) both reads: with a
        write between two reads of a pool the compiler keeps the pool of
        before for the first reader and copies it whole (PR 32)."""
        from ..ops import latent_attention as la

        B = self.B
        if B:
            pool = la.write_latent_rows(pool, rows[:B], self.tables, self.dpos)
        if self.C:
            pool = la.write_latent_chunk(pool, rows[B:], self.table,
                                         self.start)
        return pool

    def attend(self, q, pool):
        from ..ops.latent_attention import latent_attention

        cfg, B, out = self.cfg, self.B, []
        kw = dict(v_width=cfg.kv_lora_rank, scale=cfg.softmax_scale,
                  qscale=(cfg.llama_4_scaling_beta,
                          cfg.rope_original_max_position_embeddings))
        with jax.named_scope("latent_attend"):
            if B:
                out.append(latent_attention(q[:B, None], pool, self.tables,
                                            self.dpos, **kw)[:, 0])
            if self.C:
                out.append(latent_attention(
                    q[None, B:], pool, self.table[None],
                    jnp.reshape(self.start, (1,)).astype(jnp.int32),
                    **kw)[0])
        return jnp.concatenate(out, axis=0)


class Mistral4DecoderLayer(Layer):
    def __init__(self, cfg: Mistral4Config):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = Mistral4Attention(cfg)
        self.post_attention_layernorm = LlamaRMSNorm(cfg.hidden_size,
                                                     cfg.rms_norm_eps)
        shared = None
        if cfg.n_shared_experts:
            shared = LlamaMLP(types.SimpleNamespace(
                hidden_size=cfg.hidden_size,
                intermediate_size=(cfg.n_shared_experts
                                   * cfg.moe_intermediate_size),
                sequence_parallel=False, context_parallel=False))
        self.mlp = HeldExpertsLayer(
            cfg.hidden_size, cfg.moe_intermediate_size, cfg.n_routed_experts,
            cfg.num_experts_per_tok, experts_held=cfg.experts_held,
            shared=shared, rule=deepseek_v3_rule(
                cfg.norm_topk_prob, cfg.routed_scaling_factor),
            selection_bias=True, init_std=cfg.initializer_range,
            dtype=cfg.dtype)

    def _experts(self, x, valid=None):
        y, counts = self.mlp(self.post_attention_layernorm(Tensor(x)).value,
                             valid)
        return x + y, counts

    def dense(self, x, absorbed: bool = True):
        """x (S, H) raw, a whole sequence, no cache."""
        h = self.input_layernorm(Tensor(x)).value
        return self._experts(x + self.self_attn.dense(h, absorbed))[0]

    def serve(self, x, pool, step, valid):
        """The rows x (T, H) of one served step. Returns (x, new pool, the
        expert layer's row counts)."""
        a, pool = self.self_attn.paged(
            self.input_layernorm(Tensor(x)).value, pool, step)
        x, counts = self._experts(x + a, valid)
        return x, pool, counts


class Mistral4Model(Layer):
    def __init__(self, cfg: Mistral4Config):
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
        self.layers = LayerList([cast(Mistral4DecoderLayer(cfg))
                                 for _ in range(cfg.num_hidden_layers)])
        self.norm = cast(LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps))
        self._counts = []

    def _embed(self, ids):
        return jnp.take(_val(self.embed_tokens.weight), ids, axis=0)

    def take_step_stats(self):
        """The expert loads the last step left: (1, layers, held + 1) int32
        — per layer the rows each held expert got and, last, the real rows'
        pairs whose expert lives elsewhere. None if no step ran since the
        last take."""
        counts, self._counts = self._counts, []
        return jnp.stack(counts)[None] if counts else None

    # ----------------------------------------------------------- cache-free
    def forward(self, input_ids, absorbed: bool = True):
        ids = input_ids.value if isinstance(input_ids, Tensor) else input_ids
        out = []
        for row in ids:                       # one sequence at a time
            x = self._embed(row)
            for layer in self.layers:
                x = layer.dense(x, absorbed)
            out.append(x)
        return self.norm(Tensor(jnp.stack(out)))

    # -------------------------------------------------------------- serving
    def _serve(self, x, pools, paged, valid):
        """All layers over the rows x (T, H) of one served step."""
        self._counts, new = [], []
        for layer, (pool,) in zip(self.layers, pools):
            x, pool, counts = layer.serve(x, pool.value, paged, valid)
            self._counts.append(counts)
            new.append((Tensor(pool),))
        return x, new

    def paged_decode_step(self, token, pools, block_tables, pos, lora=None):
        """token (B, 1), a row per slot; ``pools[i]`` = (layer i's latent
        pool,); block_tables (B, M); pos (B,). Returns (final-normed hidden
        (B, 1, H), new pools)."""
        if lora is not None:
            raise NotImplementedError("no LoRA path for this class")
        x, new = self._serve(self._embed(token.value[:, 0]), pools,
                             _Paged(self.cfg, block_tables, pos),
                             block_tables[:, 0] != 0)
        return self.norm(Tensor(x[:, None])), new

    def paged_prefill_chunk(self, input_ids, pools, block_table, start,
                            lora=None, last_idx=None):
        """One prompt chunk (1, C) at positions ``start + arange(C)``; rows
        after ``last_idx`` are padding. Returns (final-normed hidden (1, 1,
        H) of the token at ``last_idx``, new pools)."""
        if lora is not None:
            raise NotImplementedError("no LoRA path for this class")
        C = input_ids.shape[1]
        x, new = self._serve(
            self._embed(input_ids.value[0]), pools,
            _Paged(self.cfg, table=block_table, start=start, C=C),
            jnp.arange(C) <= last_idx)
        h = jax.lax.dynamic_slice_in_dim(x, last_idx, 1, 0)
        return self.norm(Tensor(h[None])), new

    def paged_decode_chunk_step(self, input_ids, pools, block_tables, pos,
                                block_table, start, last_idx):
        """Both of the above as ONE step over B + C rows (input_ids (1, B +
        C), the decode rows first), so that a tick which carries a prompt
        chunk reads every weight — the held experts' 1.6 GB a layer — once.
        Only the attention splits. Returns (final-normed hidden (1, B + 1,
        H): the decode rows, then the chunk's token at ``last_idx``; new
        pools)."""
        B = block_tables.shape[0]
        C = input_ids.shape[1] - B
        valid = jnp.concatenate([block_tables[:, 0] != 0,
                                 jnp.arange(C) <= last_idx])
        x, new = self._serve(
            self._embed(input_ids.value[0]), pools,
            _Paged(self.cfg, block_tables, pos, block_table, start, C), valid)
        h = jnp.concatenate(
            [x[:B], jax.lax.dynamic_slice_in_dim(x, B + last_idx, 1, 0)])
        return self.norm(Tensor(h[None])), new


class Mistral4ForCausalLM(Layer):
    def __init__(self, cfg: Mistral4Config):
        super().__init__()
        self.cfg = cfg
        self.model = Mistral4Model(cfg)
        self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size, bias_attr=False,
                              weight_attr=_normal(cfg.initializer_range))
        if cfg.dtype != "float32":
            from ..framework.dtype import convert_dtype

            self.lm_head._convert_dtype(convert_dtype(cfg.dtype))

    def cache_spec(self):
        """The per-layer cache declaration the serving engine is built
        from (inference/cache_spec.py): every layer one latent row a
        position."""
        from ..framework.dtype import convert_dtype
        from ..inference import cache_spec as cs

        cfg = self.cfg
        dtype = jnp.zeros((), convert_dtype(cfg.dtype)).dtype
        return cs.CacheSpec([cs.latent(cfg.latent_width)
                             for _ in range(cfg.num_hidden_layers)], dtype)

    def forward(self, input_ids, absorbed: bool = True):
        """Logits (B, S, V) of whole sequences, no cache."""
        return self.lm_head(self.model(input_ids, absorbed))
