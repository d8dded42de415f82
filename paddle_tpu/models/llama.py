"""Llama model family — the flagship for the BASELINE.json pretraining
configs (Llama-3 8B DP-only; 8B full recipe ≥40% MFU; 70B 4D hybrid).

TPU-first design decisions:
- bf16 parameters/activations, fp32 softmax + norms (master weights live in
  the optimizer, ref AdamW multi_precision).
- GQA attention through the Pallas flash kernel (ops/flash_attention.py);
  ring attention over the 'context' mesh axis for long sequences
  (parallel/ring_attention.py) when config.context_parallel.
- TP via GSPMD PartitionSpecs on weights (mp_layers pattern): qkv/gate/up
  column-sharded, o/down row-sharded over 'tensor'; embeddings vocab-sharded.
- Sequence-parallel residual stream: activations carry P('data', 'sep')
  constraints between blocks when the mesh has a 'sep' axis (ref absent —
  SURVEY §5.7 new design).

The reference has no Llama in-tree (it lives in PaddleNLP, which builds on
the surveyed primitives: fleet mp_layers + fused_multi_transformer); this
implementation targets the same recipe surface.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from ..framework.core import Tensor
from ..framework.dispatch import apply_op
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer_base import Layer
from ..nn.layer.common import Embedding, Linear
from ..nn.layer.container import LayerList
from ..parallel.api import shard_constraint
from ..tensor.manipulation import concat, reshape


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    tie_word_embeddings: bool = False
    dtype: str = "bfloat16"
    # parallelism knobs
    context_parallel: bool = False  # ring attention over 'context' axis
    sequence_parallel: bool = False  # shard activations over 'sep'
    # with sequence_parallel: attention via Ulysses head<->seq all_to_all
    # on the 'sep' axis instead of GSPMD's gather (SURVEY §5.7 optional leg)
    ulysses_parallel: bool = False
    use_flash_attention: bool = True
    # fuse lm_head matmul + CE when forward() is given labels: chunked
    # logsumexp, never materializes [B,S,V] logits (ops/fused_ce.py)
    fused_lm_head_ce: bool = True
    # tokens per fused-CE chunk: bigger chunks beat scan overhead. v5e
    # bracketed A/B on the 509M bench step (2026-08-01): 16384 -> 0.690 /
    # 0.6815 MFU vs 8192 -> 0.6752 / 0.675 — adopted. Transient f32 [c, V]
    # logits = chunk*vocab*4 B; at vocab >~100k (llama3) consider 8192 via
    # PT_CE_CHUNK unless the lm-head/CE is vocab-sharded over 'tensor'.
    ce_chunk_size: int = 16384
    recompute: bool = False
    # Mixtral-style MoE FFN (0 = dense). Experts are SwiGLU of the dense
    # MLP's shape, stacked (E, d, d_ff) and sharded over the 'expert' mesh
    # axis; routing = GShard top-k with capacity buckets + load-balance aux
    # loss folded into the LM loss (ref incubate moe_layer.py integrated at
    # model level; the reference has no model-family MoE transformer)
    moe_num_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_every: int = 1  # every Nth decoder layer gets the MoE FFN
    moe_aux_coeff: float = 0.01
    # training-side LoRA flag: rank > 0 wraps the projection Linears with
    # trainable A/B factors at construction (nn/lora.py). The SERVING
    # multi-adapter path is orthogonal — it threads pooled factors through
    # the paged programs per request and wants a CLEAN base model.
    lora_rank: int = 0
    lora_alpha: Optional[float] = None
    lora_targets: Optional[tuple] = None  # default: all seven projections


# attribute names attach_lora/merge_lora wrap when cfg.lora_targets is None
LLAMA_LORA_TARGETS = ("q_proj", "k_proj", "v_proj", "o_proj",
                      "gate_proj", "up_proj", "down_proj")


def llama3_8b_config(**kw) -> LlamaConfig:
    return LlamaConfig(**{**dict(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_hidden_layers=32, num_attention_heads=32, num_key_value_heads=8), **kw})


def llama3_70b_config(**kw) -> LlamaConfig:
    return LlamaConfig(**{**dict(
        vocab_size=128256, hidden_size=8192, intermediate_size=28672,
        num_hidden_layers=80, num_attention_heads=64, num_key_value_heads=8), **kw})


def llama_tiny_config(**kw) -> LlamaConfig:
    return LlamaConfig(**{**dict(
        vocab_size=1024, hidden_size=256, intermediate_size=704,
        num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=4,
        max_position_embeddings=512, dtype="float32"), **kw})


# --------------------------------------------------------------------------- #
# RoPE
# --------------------------------------------------------------------------- #


def _rope_tables(head_dim: int, max_pos: int, theta: float):
    inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    t = jnp.arange(max_pos, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # (S, D/2)
    return jnp.cos(freqs), jnp.sin(freqs)


def _rope_rotate(x, c, s):
    """Rotate pairs (x[..., :D/2], x[..., D/2:]) by pre-gathered c/s rows."""
    d2 = x.shape[-1] // 2
    xf1 = x[..., :d2].astype(jnp.float32)
    xf2 = x[..., d2:].astype(jnp.float32)
    out = jnp.concatenate([xf1 * c - xf2 * s, xf2 * c + xf1 * s], axis=-1)
    return out.astype(x.dtype)


def _apply_rope_rows(x, cos, sin, pos):
    """x: (B, 1, H, D), pos: int32 [B] — per-row rope rotation (continuous
    batching: each row sits at its own position)."""
    c = jnp.take(cos, pos, axis=0)[:, None, None, :]
    s = jnp.take(sin, pos, axis=0)[:, None, None, :]
    return _rope_rotate(x, c, s)


def _apply_rope_window(x, cos, sin, pos):
    """x: (B, W, H, D) at positions ``pos[:, None] + arange(W)`` with
    per-row int32 ``pos`` (speculative verify window: every slot's window
    starts at its own depth). Edge-clamped like :func:`_apply_rope_chunk`:
    rows past the table are masked window surplus the harvest discards."""
    W = x.shape[1]
    idx = jnp.clip(pos[:, None] + jnp.arange(W)[None, :], 0,
                   cos.shape[0] - 1)                     # (B, W)
    c = jnp.take(cos, idx, axis=0)[:, :, None, :]
    s = jnp.take(sin, idx, axis=0)[:, :, None, :]
    return _rope_rotate(x, c, s)


def _apply_rope_chunk(x, cos, sin, start):
    """x: (B, C, H, D) at positions ``start + arange(C)`` with traced
    ``start`` (chunked prefill). Per-row gather with edge-clamp instead of
    a dynamic_slice: a padded final chunk may overrun the rope table, and
    dynamic_slice would CLAMP the start down, mis-rotating the real
    positions — clamped rows here are only ever discarded padding."""
    S = x.shape[1]
    idx = jnp.clip(start + jnp.arange(S), 0, cos.shape[0] - 1)
    c = jnp.take(cos, idx, axis=0)[None, :, None, :]
    s = jnp.take(sin, idx, axis=0)[None, :, None, :]
    return _rope_rotate(x, c, s)


def _apply_rope(x, cos, sin, pos_offset=0):
    """x: (B, S, H, D); rotate pairs (x[..., :D/2], x[..., D/2:])."""
    S = x.shape[1]
    c = jax.lax.dynamic_slice_in_dim(cos, pos_offset, S, 0)[None, :, None, :]
    s = jax.lax.dynamic_slice_in_dim(sin, pos_offset, S, 0)[None, :, None, :]
    return _rope_rotate(x, c, s)


def _apply_rope_bhsd(x, cos, sin, pos_offset=0):
    """x: (B, H, S, D) — the kernel-native head-major layout."""
    S = x.shape[2]
    c = jax.lax.dynamic_slice_in_dim(cos, pos_offset, S, 0)[None, None, :, :]
    s = jax.lax.dynamic_slice_in_dim(sin, pos_offset, S, 0)[None, None, :, :]
    return _rope_rotate(x, c, s)


def _write_rows_kv(qv, kv, vv, pool, cos, sin, block_tables, pos):
    """B single-token rows against the paged pool, first half: rope at each
    row's own ``pos`` and K/V scattered through the row's block table. qv
    (B, 1, H, D); ``pool`` the layer's arrays — (kp, vp), or (kq, ks, vq,
    vs) for int8 KV. Returns (rotated q, new pool)."""
    from ..ops import paged_attention as pa

    qr = _apply_rope_rows(qv, cos, sin, pos)
    kr = _apply_rope_rows(kv, cos, sin, pos)
    write = pa.write_decode_kv_q if len(pool) == 4 else pa.write_decode_kv
    return qr, write(*pool, kr[:, 0], vv[:, 0], block_tables, pos)


def _attend_rows(qr, pool, block_tables, pos):
    """Second half: each row's attention over the context its table names,
    (B, 1, H, D)."""
    from ..ops import paged_attention as pa

    attend = (pa.paged_decode_attention_q if len(pool) == 4
              else pa.paged_decode_attention)
    return attend(qr, *pool, block_tables, pos)


def _write_chunk_kv(qv, kv, vv, pool, cos, sin, block_table, start):
    """One prompt chunk (1, C, H, D) at positions ``start + arange(C)``,
    first half: rope and its K/V into consecutive entries of
    ``block_table``. ``pool`` and the return as in :func:`_write_rows_kv`."""
    from ..ops import paged_attention as pa

    qr = _apply_rope_chunk(qv, cos, sin, start)
    kr = _apply_rope_chunk(kv, cos, sin, start)
    write = pa.write_chunk_kv_q if len(pool) == 4 else pa.write_chunk_kv
    return qr, write(*pool, kr[0], vv[0], block_table, start)


def _attend_chunk(qr, pool, block_table, start):
    """Second half: causal attention over everything written so far,
    (1, C, H, D)."""
    from ..ops import paged_attention as pa

    attend = (pa.paged_prefill_attention_q if len(pool) == 4
              else pa.paged_prefill_attention)
    return attend(qr, *pool, block_table, start)


def _rows_and_chunk_attention(block_tables, pos, block_table, start):
    """The attention of a step over B decode rows and one prompt chunk, for
    every layer of ONE program trace: ``attend(q, k, v, cos, sin, *pool) ->
    (out, *new pool)`` with q/k/v (1, B + C, heads, D), the decode rows
    first. The rows take :meth:`LlamaAttention.paged_decode`'s path, the
    chunk :meth:`~LlamaAttention.paged_prefill_chunk`'s, the same writes and
    kernels. No row reads what another row of the call writes (a decode
    row's and the chunk's private blocks are different blocks), so the order
    is free — and it is BOTH writes, then both attentions over the pool as
    it then stands: with a write between the two reads the compiler has to
    keep the pool of before for the first reader and copies it whole (134
    MB a pool at the Mistral cells' size, found in the compiled text and in
    the trace: PERF.md, PR 32).

    It is jitted, and made anew for each trace of the step: jit keys a trace
    on the function's identity and the operands' shapes, which are the same
    in every layer, so the two kernels are traced and lowered once a program
    instead of once a layer (every ``pallas_call`` is a new function to jit:
    0.14–0.30 s a call site on the chip's host, 7 of the joint program's 14
    s), and nothing of one trace (kernel mode, mesh, geometry) can leak into
    another server's."""
    B = block_tables.shape[0]

    def attend(qv, kv, vv, cos, sin, tables, posv, table, startv, *pool):
        qd, pool = _write_rows_kv(qv[0, :B, None], kv[0, :B, None],
                                  vv[0, :B, None], pool, cos, sin, tables,
                                  posv)
        qc, pool = _write_chunk_kv(qv[:, B:], kv[:, B:], vv[:, B:], pool, cos,
                                   sin, table, startv)
        rows = _attend_rows(qd, pool, tables, posv)
        chunk = _attend_chunk(qc, pool, table, startv)
        return (jnp.concatenate([rows[None, :, 0], chunk], axis=1), *pool)

    attend = jax.jit(attend)
    return lambda qv, kv, vv, cos, sin, *pool: attend(
        qv, kv, vv, cos, sin, block_tables, pos, block_table, start, *pool)


# --------------------------------------------------------------------------- #
# Context-parallel attention dispatch
# --------------------------------------------------------------------------- #


def _attn_island(axis, local, qr, kr, vv, head_divisible=False):
    """Shared scaffolding for attention shard_map islands.

    The sequence-axis collectives (``ppermute`` for the ring,
    ``all_to_all`` for Ulysses) need a *bound* mesh axis name. Inside an
    outer shard_map (manual-SPMD callers) the direct ``local`` call
    succeeds. Under GSPMD jit (ParallelEngine) no axis is bound, so when
    the active mesh carries ``axis`` we open a shard_map island: batch
    over 'data', sequence over ``axis``, heads over 'tensor' when present
    (CP×TP / SP×TP composition falls out of the head sharding). Returns
    None when the axis exists nowhere — the caller falls back to plain
    attention (single-device parity runs).

    ``head_divisible``: Ulysses additionally needs local head counts
    divisible by the axis size; an explicit user request that can't be
    honored warns instead of silently degrading.
    """
    try:
        # explicit binding probe: axis_index raises NameError iff `axis` is
        # not bound here. Probing with the tiny op (instead of running
        # `local` and catching ITS NameError) keeps a genuine NameError bug
        # inside the ring/Ulysses kernels loud instead of silently
        # rerouting to a different attention path (ADVICE r4).
        jax.lax.axis_index(axis)
        bound = True
    except NameError:
        bound = False
    if bound:
        return local(qr, kr, vv)  # already inside a shard_map binding axis
    from ..parallel.api import current_mesh, in_spmd_region

    mesh = current_mesh()
    if (mesh is None or axis not in mesh.shape or mesh.shape[axis] <= 1
            or not in_spmd_region()):
        return None
    tp = "tensor" if ("tensor" in mesh.shape and mesh.shape["tensor"] > 1) \
        else None
    if head_divisible:
        n = mesh.shape[axis]
        tpn = mesh.shape[tp] if tp else 1
        h, hkv = qr.shape[2], kr.shape[2]
        if h % tpn or hkv % tpn or (h // tpn) % n or (hkv // tpn) % n:
            import warnings

            warnings.warn(
                f"ulysses_parallel requested but head counts {h}/{hkv} are "
                f"not divisible by the '{axis}' axis ({n}"
                f"{f' x tensor {tpn}' if tp else ''}); falling back to "
                f"GSPMD attention", UserWarning)
            return None
    dp = "data" if "data" in mesh.shape else None
    spec = P(dp, axis, tp, None)
    return _shard_map_qkv(local, mesh, spec, qr, kr, vv)


def _shard_map_qkv(local, mesh, spec, q, k, v):
    from ..ops.select import pallas_interpret

    # the pallas HLO interpreter's internal dynamic_slice doesn't propagate
    # varying-mesh-axes types; compiled runs keep the default check
    kw = {"check_vma": False} if pallas_interpret() else {}
    return jax.shard_map(local, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, **kw)(q, k, v)


def _flash_bhsd(qh, kh, vh, causal):
    """``flash_attention`` on (B, H, S, D) operands. Under a GSPMD-
    partitioned trace (ParallelEngine on a multi-device mesh) a Mosaic
    kernel cannot be partitioned automatically, so when the kernel would
    be selected the call sits in a shard_map island: batch over the
    data-like axes that divide it, heads over 'tensor' when both head
    counts divide. Everything else takes the plain call, whose own rule
    then picks the jnp composition under partitioning."""
    from ..ops.flash_attention import flash_attention
    from ..ops.select import XLA, partitioned, select_flash_attention
    from ..parallel.api import current_mesh

    def local(a, b, c):
        return flash_attention(a, b, c, causal=causal)

    if not partitioned() or select_flash_attention(
            qh.shape, kh.shape, is_partitioned=False) == XLA:
        return local(qh, kh, vh)
    mesh = current_mesh()
    batch, n = [], 1
    for ax in ("data", "sharding"):
        size = mesh.shape.get(ax, 1)
        if size > 1 and qh.shape[0] % (n * size) == 0:
            batch.append(ax)
            n *= size
    tpn = mesh.shape.get("tensor", 1)
    tp = "tensor" if (tpn > 1 and qh.shape[1] % tpn == 0
                      and kh.shape[1] % tpn == 0) else None
    spec = P(tuple(batch) or None, tp, None, None)
    return _shard_map_qkv(local, mesh, spec, qh, kh, vh)


def _ring_dispatch(qr, kr, vv, rep, use_flash, causal):
    """Ring attention over the 'context' axis (SURVEY §5.7 new design —
    the reference has no context parallelism at all, grep-verified)."""

    def local(a, b, c):
        from ..ops.flash_attention import _use_pallas
        from ..parallel.ring_attention import ring_attention_bshd
        from ..parallel.ring_flash_attention import ring_flash_attention_bshd

        if use_flash and _use_pallas():
            # Pallas blockwise kernels per ring hop, GQA-native
            return ring_flash_attention_bshd(a, b, c, "context", causal=causal)
        kx = jnp.repeat(b, rep, axis=2) if rep > 1 else b
        vx = jnp.repeat(c, rep, axis=2) if rep > 1 else c
        return ring_attention_bshd(a, kx, vx, "context", causal=causal)

    return _attn_island("context", local, qr, kr, vv)


def _ulysses_dispatch(qr, kr, vv, use_flash, causal):
    """Ulysses sequence parallelism at the model level (SURVEY §5.7
    optional leg; ref absent): all_to_all swaps the sharded dim seq→heads,
    full-sequence attention runs on the local head slice, and a second
    all_to_all swaps back. GQA needs no handling here — the flash kernel
    and the dense reference both route shared KV heads internally."""

    def attn_fn(a, b, c):
        from ..ops.flash_attention import _use_pallas, flash_attention_bshd

        if use_flash and _use_pallas():
            return flash_attention_bshd(a, b, c, causal=causal)
        from ..ops.flash_attention import _ref_bhsd

        out = _ref_bhsd(jnp.swapaxes(a, 1, 2), jnp.swapaxes(b, 1, 2),
                        jnp.swapaxes(c, 1, 2), causal,
                        1.0 / math.sqrt(a.shape[-1]))
        return jnp.swapaxes(out, 1, 2)

    def local(a, b, c):
        from ..parallel.ring_attention import ulysses_attention_bshd

        return ulysses_attention_bshd(a, b, c, "sep", causal=causal,
                                      attn_fn=attn_fn)

    return _attn_island("sep", local, qr, kr, vv, head_divisible=True)


# --------------------------------------------------------------------------- #
# Modules
# --------------------------------------------------------------------------- #


class LlamaRMSNorm(Layer):
    def __init__(self, hidden_size, eps):
        super().__init__()
        from ..nn.initializer import Constant

        self.weight = self.create_parameter([hidden_size],
                                            default_initializer=Constant(1.0))
        self.weight.pspec = P()
        self._eps = eps

    def forward(self, x):
        from ..ops.fused_norm import fused_rms_norm

        return apply_op(lambda v, w: fused_rms_norm(v, w, self._eps), x, self.weight,
                        op_name="rms_norm")


class LlamaAttention(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.num_heads = cfg.num_attention_heads
        self.num_kv_heads = cfg.num_key_value_heads
        self.head_dim = cfg.hidden_size // cfg.num_attention_heads
        h = cfg.hidden_size
        init = Normal(0.0, 0.02)
        self.q_proj = Linear(h, self.num_heads * self.head_dim, bias_attr=False,
                             weight_attr=init)
        self.k_proj = Linear(h, self.num_kv_heads * self.head_dim, bias_attr=False,
                             weight_attr=init)
        self.v_proj = Linear(h, self.num_kv_heads * self.head_dim, bias_attr=False,
                             weight_attr=init)
        self.o_proj = Linear(self.num_heads * self.head_dim, h, bias_attr=False,
                             weight_attr=init)
        # TP shardings (mp_layers pattern: column for qkv, row for o)
        self.q_proj.weight.pspec = P(None, "tensor")
        self.k_proj.weight.pspec = P(None, "tensor")
        self.v_proj.weight.pspec = P(None, "tensor")
        self.o_proj.weight.pspec = P("tensor", None)

    def _qkv(self, x, B, S, lora=None):
        """q/k/v projections. The int8 decode path can fuse the three into
        ONE concatenated matmul (quantize_int8 with PT_W8_FUSED_QKV=1 —
        single weight stream + kernel launch per step; an earlier A/B
        measured a tie, ROADMAP C6). ``lora``: per-layer dict of gathered
        per-row (A, B, scale) factors keyed "q"/"k"/"v" (serving
        multi-adapter path) — the delta is additive AFTER the base
        projection, so it composes with both the fp and fused-int8
        branches."""
        if getattr(self, "_w8_split", None):
            from ..ops.int8 import w8_matmul

            nq, nk, nv = self._w8_split

            def qkv8(v, wq, s):
                o = w8_matmul(v, wq, s)
                return o[..., :nq], o[..., nq:nq + nk], o[..., nq + nk:]

            q, k, v = apply_op(qkv8, x, self.qkv_fused.weight_q,
                               self.qkv_fused.weight_scale,
                               op_name="w8_qkv")
        elif lora is not None:
            # base matmul + gathered delta fused into ONE op per projection
            # (a single Pallas program per row under use_pallas())
            from ..nn.lora import lora_matmul

            q = lora_matmul(x, self.q_proj.weight, lora.get("q"))
            k = lora_matmul(x, self.k_proj.weight, lora.get("k"))
            v = lora_matmul(x, self.v_proj.weight, lora.get("v"))
        else:
            q, k, v = self.q_proj(x), self.k_proj(x), self.v_proj(x)
        if lora is not None and getattr(self, "_w8_split", None):
            from ..nn.lora import bgmv

            if "q" in lora:
                q = q + bgmv(x, lora["q"])
            if "k" in lora:
                k = k + bgmv(x, lora["k"])
            if "v" in lora:
                v = v + bgmv(x, lora["v"])
        return (reshape(q, [B, S, self.num_heads, self.head_dim]),
                reshape(k, [B, S, self.num_kv_heads, self.head_dim]),
                reshape(v, [B, S, self.num_kv_heads, self.head_dim]))

    def _o_lora(self, out, lora):
        """Output projection plus the gathered per-row "o" delta, fused."""
        if lora is not None:
            from ..nn.lora import lora_matmul

            return lora_matmul(out, self.o_proj.weight, lora.get("o"))
        return self.o_proj(out)

    def forward(self, x, cos, sin, cache=None, pos_offset=0):
        B, S = x.shape[0], x.shape[1]
        q, k, v = self._qkv(x, B, S)

        def attn(qv, kv, vv, cv, sv, *cache_vals):
            qv = checkpoint_name(qv, "qkv")
            kv = checkpoint_name(kv, "qkv")
            vv = checkpoint_name(vv, "qkv")
            if (self.cfg.use_flash_attention and not cache_vals
                    and not self.cfg.context_parallel
                    and not (self.cfg.sequence_parallel
                             and self.cfg.ulysses_parallel)):
                # BHSD-NATIVE training path: swap to head-major BEFORE rope
                # so the layout change fuses into the rope elementwise (and
                # the inverse transposes fold into the o-proj/vjp dots) —
                # at S=16k the standalone (B,S,H,D)<->(B,H,S,D) copies
                # around the custom call were ~33% of the step (r5 per-op
                # profile)
                qh = _apply_rope_bhsd(jnp.swapaxes(qv, 1, 2), cv, sv,
                                      pos_offset)
                kh = _apply_rope_bhsd(jnp.swapaxes(kv, 1, 2), cv, sv,
                                      pos_offset)
                out = _flash_bhsd(qh, kh, jnp.swapaxes(vv, 1, 2),
                                  causal=True)
                return jnp.swapaxes(out, 1, 2)
            qr = _apply_rope(qv, cv, sv, pos_offset)
            kr = _apply_rope(kv, cv, sv, pos_offset)
            if cache_vals:
                ck, cvv = cache_vals
                kr = jnp.concatenate([ck, kr], axis=1)
                vv = jnp.concatenate([cvv, vv], axis=1)
            causal = cache_vals == ()
            rep = self.num_heads // self.num_kv_heads
            from ..ops.flash_attention import flash_attention_bshd

            if self.cfg.context_parallel and not cache_vals:
                ring_out = _ring_dispatch(qr, kr, vv, rep,
                                          self.cfg.use_flash_attention, causal)
                if ring_out is not None:
                    return ring_out
            if self.cfg.sequence_parallel and self.cfg.ulysses_parallel \
                    and not cache_vals:
                uly_out = _ulysses_dispatch(
                    qr, kr, vv, self.cfg.use_flash_attention, causal)
                if uly_out is not None:
                    return uly_out
            if self.cfg.use_flash_attention:
                # GQA handled inside the kernel (no KV repeat)
                return flash_attention_bshd(qr, kr, vv, causal=causal)
            if rep > 1:
                kr = jnp.repeat(kr, rep, axis=2)
                vv = jnp.repeat(vv, rep, axis=2)
            d = qr.shape[-1]
            logits = jnp.einsum("bshd,bthd->bhst", qr, kr).astype(jnp.float32) \
                / math.sqrt(d)
            if causal:
                mask = jnp.tril(jnp.ones((S, kr.shape[1]), bool), k=kr.shape[1] - S)
                logits = jnp.where(mask, logits, -1e30)
            p = jax.nn.softmax(logits, -1).astype(qr.dtype)
            return jnp.einsum("bhst,bthd->bshd", p, vv)

        args = [q, k, v, Tensor(cos), Tensor(sin)]
        if cache is not None:
            args += [cache[0], cache[1]]
        # remat-policy anchor (engine save_attn/offload_attn policies): the
        # flash output is the one S²-cost intermediate worth pinning — named
        # inside the op so eager decode pays no extra dispatch
        out = apply_op(lambda *a: checkpoint_name(attn(*a), "attn_out"),
                       *args, op_name="flash_attention")
        out = reshape(out, [B, S, self.num_heads * self.head_dim])
        out = self.o_proj(out)
        if self.cfg.sequence_parallel:
            out = shard_constraint(out, P("data", "sep", None))
        elif self.cfg.context_parallel:
            out = shard_constraint(out, P("data", "context", None))
        return out

    def prefill(self, x, cos, sin, ck, cv):
        """Prompt pass that fills the fixed decode caches at positions
        [0, S): ONE causal attention over the whole prompt (flash kernel
        when enabled) instead of S single-token decode steps — prompt
        processing at training-forward speed."""
        B, S = x.shape[0], x.shape[1]
        q, k, v = self._qkv(x, B, S)

        def step(qv, kv, vv, ckv, cvv, cosv, sinv):
            qr = _apply_rope(qv, cosv, sinv, 0)
            kr = _apply_rope(kv, cosv, sinv, 0)
            ckv = jax.lax.dynamic_update_slice(ckv, kr.astype(ckv.dtype),
                                               (0, 0, 0, 0))
            cvv = jax.lax.dynamic_update_slice(cvv, vv.astype(cvv.dtype),
                                               (0, 0, 0, 0))
            rep = self.num_heads // self.num_kv_heads
            if self.cfg.use_flash_attention:
                from ..ops.flash_attention import flash_attention_bshd

                out = flash_attention_bshd(qr, kr, vv, causal=True)
            else:
                kx = jnp.repeat(kr, rep, axis=2) if rep > 1 else kr
                vx = jnp.repeat(vv, rep, axis=2) if rep > 1 else vv
                d = qr.shape[-1]
                logits = jnp.einsum("bshd,bthd->bhst", qr, kx).astype(
                    jnp.float32) / math.sqrt(d)
                mask = jnp.tril(jnp.ones((S, S), bool))
                logits = jnp.where(mask, logits, -1e30)
                p = jax.nn.softmax(logits, -1).astype(qr.dtype)
                out = jnp.einsum("bhst,bthd->bshd", p, vx)
            return out, ckv, cvv

        out, ck, cv = apply_op(step, q, k, v, ck, cv, Tensor(cos), Tensor(sin),
                               op_name="prefill_attention")
        out = reshape(out, [B, S, self.num_heads * self.head_dim])
        return self.o_proj(out), ck, cv

    def decode(self, x, cos, sin, ck, cv, pos):
        """Single-token decode with a fixed-size KV cache: write the new
        K/V at ``pos`` via dynamic_update_slice (static shapes, so the whole
        generate loop compiles once) and attend over positions ≤ pos.
        ck/cv: Tensors (B, L, KV, D); pos: traced int32 scalar, or an int32
        [B] VECTOR of per-row positions (continuous-batching serving: every
        slot sits at its own depth — rope rows are gathered and cache writes
        scattered per row)."""
        B = x.shape[0]
        H, KV, D = self.num_heads, self.num_kv_heads, self.head_dim
        q, k, v = self._qkv(x, B, 1)

        def step(qv, kv, vv, ckv, cvv, cosv, sinv):
            vector_pos = jnp.ndim(pos) == 1
            if vector_pos:
                qr = _apply_rope_rows(qv, cosv, sinv, pos)
                kr = _apply_rope_rows(kv, cosv, sinv, pos)
                rows = jnp.arange(B)
                ckv = ckv.at[rows, pos].set(kr[:, 0].astype(ckv.dtype))
                cvv = cvv.at[rows, pos].set(vv[:, 0].astype(cvv.dtype))
            else:
                qr = _apply_rope(qv, cosv, sinv, pos)
                kr = _apply_rope(kv, cosv, sinv, pos)
                ckv = jax.lax.dynamic_update_slice(ckv, kr.astype(ckv.dtype),
                                                   (0, pos, 0, 0))
                cvv = jax.lax.dynamic_update_slice(cvv, vv.astype(cvv.dtype),
                                                   (0, pos, 0, 0))
            rep = H // KV
            L = ckv.shape[1]
            # GQA-native: group q heads by kv head — no L-sized cache copies
            qg = qr.reshape(B, 1, KV, rep, D)
            scores = jnp.einsum("bsgrd,btgd->bgrst", qg, ckv).astype(
                jnp.float32) / math.sqrt(D)
            if vector_pos:
                mask = (jnp.arange(L)[None, :] <=
                        pos[:, None])[:, None, None, None, :]
            else:
                mask = (jnp.arange(L) <= pos)[None, None, None, None, :]
            scores = jnp.where(mask, scores, -1e30)
            p = jax.nn.softmax(scores, -1).astype(qr.dtype)
            out = jnp.einsum("bgrst,btgd->bsgrd", p, cvv)
            return out.reshape(B, 1, H, D), ckv, cvv

        out, ck, cv = apply_op(step, q, k, v, ck, cv, Tensor(cos), Tensor(sin),
                               op_name="decode_attention")
        out = reshape(out, [B, 1, H * D])
        return self.o_proj(out), ck, cv

    def paged_decode(self, x, cos, sin, pool, block_tables, pos,
                     lora=None):
        """Single-token decode against the PAGED pool: K/V of the new token
        scatter through the block table at ``pos``; attention gathers
        context by table (ops/paged_attention.py). ``pool``: per-layer
        tuple of Tensors — ``(kp, vp)`` f32/bf16 pools
        (num_blocks, bs, KV, D), or ``(kq, ks, vq, vs)`` int8 pools + f32
        per-block-per-head scales (kv_quant="int8": dequant is fused into
        the attention, the pool is never materialized in full precision);
        block_tables: traced int32 (B, M); pos: traced int32 [B].
        Numerically mirrors the dense vector-pos ``decode`` so paged/dense
        greedy outputs agree token-exactly."""
        B = x.shape[0]
        H, D = self.num_heads, self.head_dim
        q, k, v = self._qkv(x, B, 1, lora=lora)

        def step(qv, kv, vv, *rest):
            *pl, cosv, sinv = rest
            qr, pl = _write_rows_kv(qv, kv, vv, pl, cosv, sinv, block_tables,
                                    pos)
            return (_attend_rows(qr, pl, block_tables, pos), *pl)

        out, *pool = apply_op(step, q, k, v, *pool, Tensor(cos), Tensor(sin),
                              op_name="paged_decode_attention")
        out = reshape(out, [B, 1, H * D])
        return self._o_lora(out, lora), tuple(pool)

    def paged_verify_attn(self, x, cos, sin, pool, block_tables, pos,
                          lora=None):
        """Multi-token speculative VERIFY window against the paged pool:
        K/V for all W = k+1 window tokens scatter through the block table
        at ``pos..pos+k``; attention gathers context by table with the
        in-window causal mask (query j sees positions ≤ pos+j). x:
        (B, W, hidden); ``pool`` as in :meth:`paged_decode`; block_tables:
        traced int32 (B, M); pos: traced int32 [B]. At W = 1 this is
        numerically the paged ``decode`` — which is what makes greedy
        speculative output token-exact vs the dense server."""
        B, W = x.shape[0], x.shape[1]
        H, D = self.num_heads, self.head_dim
        q, k, v = self._qkv(x, B, W, lora=lora)

        if len(pool) == 4:
            def step(qv, kv, vv, kqv, ksv, vqv, vsv, cosv, sinv):
                from ..ops.paged_attention import (paged_verify_attention_q,
                                                   write_window_kv_q)

                qr = _apply_rope_window(qv, cosv, sinv, pos)
                kr = _apply_rope_window(kv, cosv, sinv, pos)
                kqv, ksv, vqv, vsv = write_window_kv_q(
                    kqv, ksv, vqv, vsv, kr, vv, block_tables, pos)
                out = paged_verify_attention_q(qr, kqv, ksv, vqv, vsv,
                                               block_tables, pos)
                return out, kqv, ksv, vqv, vsv
        else:
            def step(qv, kv, vv, kpv, vpv, cosv, sinv):
                from ..ops.paged_attention import (paged_verify_attention,
                                                   write_window_kv)

                qr = _apply_rope_window(qv, cosv, sinv, pos)
                kr = _apply_rope_window(kv, cosv, sinv, pos)
                kpv, vpv = write_window_kv(kpv, vpv, kr, vv, block_tables,
                                           pos)
                out = paged_verify_attention(qr, kpv, vpv, block_tables, pos)
                return out, kpv, vpv

        out, *pool = apply_op(step, q, k, v, *pool, Tensor(cos), Tensor(sin),
                              op_name="paged_verify_attention")
        out = reshape(out, [B, W, H * D])
        return self._o_lora(out, lora), tuple(pool)

    def paged_prefill_chunk(self, x, cos, sin, pool, block_table, start,
                            lora=None):
        """One fixed-size prefill CHUNK through the paged pool: queries sit
        at positions ``start + arange(C)`` (``start`` traced, block-aligned,
        C a multiple of the block size), their K/V scatter into consecutive
        table entries, and attention runs against ALL paged context written
        so far (earlier chunks + shared prefix blocks) with a causal mask.
        x: (1, C, hidden); ``pool`` as in :meth:`paged_decode`;
        block_table: traced int32 (M,)."""
        B, S = x.shape[0], x.shape[1]
        H, D = self.num_heads, self.head_dim
        q, k, v = self._qkv(x, B, S, lora=lora)

        def step(qv, kv, vv, *rest):
            *pl, cosv, sinv = rest
            qr, pl = _write_chunk_kv(qv, kv, vv, pl, cosv, sinv, block_table,
                                     start)
            return (_attend_chunk(qr, pl, block_table, start), *pl)

        out, *pool = apply_op(step, q, k, v, *pool, Tensor(cos), Tensor(sin),
                              op_name="paged_prefill_attention")
        out = reshape(out, [B, S, H * D])
        return self._o_lora(out, lora), tuple(pool)

    def paged_decode_chunk(self, x, cos, sin, pool, attend):
        """A tick's B decode rows AND one prompt chunk of C tokens through
        ONE q/k/v and ONE output projection, so that the layer's weights
        are read once for both. x: (1, B + C, hidden), the decode rows
        first; only the attention splits, in ``attend`` — the step's one
        :func:`_rows_and_chunk_attention`, which knows the rows' tables and
        positions and the chunk's — and its two outputs come back joined
        for ``o_proj``."""
        S = x.shape[1]
        q, k, v = self._qkv(x, 1, S)
        out, *pool = apply_op(attend, q, k, v, Tensor(cos), Tensor(sin),
                              *pool, op_name="paged_decode_chunk_attention")
        out = reshape(out, [1, S, self.num_heads * self.head_dim])
        return self.o_proj(out), tuple(pool)


class LlamaMLP(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        init = Normal(0.0, 0.02)
        self.gate_proj = Linear(cfg.hidden_size, cfg.intermediate_size, bias_attr=False,
                                weight_attr=init)
        self.up_proj = Linear(cfg.hidden_size, cfg.intermediate_size, bias_attr=False,
                              weight_attr=init)
        self.down_proj = Linear(cfg.intermediate_size, cfg.hidden_size, bias_attr=False,
                                weight_attr=init)
        self.gate_proj.weight.pspec = P(None, "tensor")
        self.up_proj.weight.pspec = P(None, "tensor")
        self.down_proj.weight.pspec = P("tensor", None)
        self._sp = cfg.sequence_parallel
        self._cp = cfg.context_parallel

    def forward(self, x, lora=None):
        from ..nn.quant import Int8Linear

        if isinstance(self.gate_proj, Int8Linear):  # weight-only decode mode
            if lora is not None:
                raise NotImplementedError(
                    "pooled LoRA deltas on a weight-only int8 MLP are not "
                    "supported — serve LoRA over fp base weights (int8 KV "
                    "quant is fine)")
            from ..ops.int8 import w8_matmul

            def mlp8(v, wgq, sg, wuq, su, wdq, sd):
                h = jax.nn.silu(w8_matmul(v, wgq, sg)) * w8_matmul(v, wuq, su)
                return checkpoint_name(w8_matmul(h, wdq, sd), "mlp_out")

            out = apply_op(mlp8, x,
                           self.gate_proj.weight_q, self.gate_proj.weight_scale,
                           self.up_proj.weight_q, self.up_proj.weight_scale,
                           self.down_proj.weight_q, self.down_proj.weight_scale,
                           op_name="w8_mlp")
        elif lora is not None and any(k in lora for k in ("gate", "up", "down")):
            # decomposed SwiGLU with each base matmul + gathered per-row
            # delta fused into one op (one Pallas program per row under
            # use_pallas()); XLA re-fuses the chain inside the jitted
            # serving program
            from ..nn.lora import lora_matmul

            g = lora_matmul(x, self.gate_proj.weight, lora.get("gate"))
            u = lora_matmul(x, self.up_proj.weight, lora.get("up"))
            h = apply_op(lambda a, b: jax.nn.silu(a) * b, g, u,
                         op_name="swiglu")
            out = lora_matmul(h, self.down_proj.weight, lora.get("down"))
        elif not isinstance(self.gate_proj, Linear):
            # training-side LoRALinear wrap (attach_lora): go through the
            # layer calls so each projection applies its own A/B residual
            h = apply_op(lambda a, b: jax.nn.silu(a) * b,
                         self.gate_proj(x), self.up_proj(x), op_name="swiglu")
            out = apply_op(lambda v: checkpoint_name(v, "mlp_out"),
                           self.down_proj(h), op_name="mlp_out")
        else:
            def mlp(v, wg, wu, wd):
                out = jnp.matmul(jax.nn.silu(jnp.matmul(v, wg)) * jnp.matmul(v, wu), wd)
                return checkpoint_name(out, "mlp_out")

            out = apply_op(mlp, x, self.gate_proj.weight, self.up_proj.weight,
                           self.down_proj.weight, op_name="linear")
        if self._sp:
            out = shard_constraint(out, P("data", "sep", None))
        elif self._cp:
            out = shard_constraint(out, P("data", "context", None))
        return out


class LlamaMoEMLP(Layer):
    """MoE FFN slot-in for LlamaMLP: top-k routed SwiGLU experts over the
    'expert' mesh axis (SURVEY §2.3 EP at model level — parity test
    `tests/test_moe_llama.py`)."""

    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        from ..incubate.distributed.models.moe import MoELayer

        self.moe = MoELayer(d_model=cfg.hidden_size,
                            num_experts=cfg.moe_num_experts,
                            d_hidden=cfg.intermediate_size,
                            top_k=cfg.moe_top_k,
                            capacity_factor=cfg.moe_capacity_factor,
                            gated_experts=True)
        self._sp = cfg.sequence_parallel
        self._cp = cfg.context_parallel

    @property
    def aux_loss(self):
        return self.moe.gate.loss

    def forward(self, x, lora=None):
        if lora is not None:
            raise NotImplementedError(
                "pooled LoRA deltas are not supported on MoE FFN layers")
        out = self.moe(x)
        out = apply_op(lambda v: checkpoint_name(v, "mlp_out"), out,
                       op_name="moe_out")
        if self._sp:
            out = shard_constraint(out, P("data", "sep", None))
        elif self._cp:
            out = shard_constraint(out, P("data", "context", None))
        return out


class LlamaDecoderLayer(Layer):
    def __init__(self, cfg: LlamaConfig, layer_idx: int = 0):
        super().__init__()
        self.input_layernorm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        self.self_attn = LlamaAttention(cfg)
        self.post_attention_layernorm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        use_moe = (cfg.moe_num_experts > 0
                   and layer_idx % max(cfg.moe_every, 1) == 0)
        self.mlp = LlamaMoEMLP(cfg) if use_moe else LlamaMLP(cfg)
        self._recompute = cfg.recompute

    def forward(self, x, cos, sin, cache=None, pos_offset=0):
        h = x + self.self_attn(self.input_layernorm(x), cos, sin, cache, pos_offset)
        out = h + self.mlp(self.post_attention_layernorm(h))
        return out

    def decode(self, x, cos, sin, ck, cv, pos):
        a, ck, cv = self.self_attn.decode(self.input_layernorm(x), cos, sin,
                                          ck, cv, pos)
        h = x + a
        out = h + self.mlp(self.post_attention_layernorm(h))
        return out, ck, cv

    def prefill(self, x, cos, sin, ck, cv):
        a, ck, cv = self.self_attn.prefill(self.input_layernorm(x), cos, sin,
                                           ck, cv)
        h = x + a
        out = h + self.mlp(self.post_attention_layernorm(h))
        return out, ck, cv

    def paged_decode(self, x, cos, sin, pool, block_tables, pos, lora=None):
        a, pool = self.self_attn.paged_decode(self.input_layernorm(x), cos,
                                              sin, pool, block_tables, pos,
                                              lora=lora)
        h = x + a
        out = h + self.mlp(self.post_attention_layernorm(h), lora=lora)
        return out, pool

    def paged_verify(self, x, cos, sin, pool, block_tables, pos, lora=None):
        a, pool = self.self_attn.paged_verify_attn(
            self.input_layernorm(x), cos, sin, pool, block_tables, pos,
            lora=lora)
        h = x + a
        out = h + self.mlp(self.post_attention_layernorm(h), lora=lora)
        return out, pool

    def paged_prefill_chunk(self, x, cos, sin, pool, block_table, start,
                            lora=None):
        a, pool = self.self_attn.paged_prefill_chunk(
            self.input_layernorm(x), cos, sin, pool, block_table, start,
            lora=lora)
        h = x + a
        out = h + self.mlp(self.post_attention_layernorm(h), lora=lora)
        return out, pool

    def paged_decode_chunk(self, x, cos, sin, pool, attend):
        a, pool = self.self_attn.paged_decode_chunk(
            self.input_layernorm(x), cos, sin, pool, attend)
        h = x + a
        out = h + self.mlp(self.post_attention_layernorm(h))
        return out, pool


class LlamaModel(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        from ..framework.dtype import convert_dtype

        if cfg.moe_num_experts > 0 and cfg.recompute:
            # the eager recompute wrapper (fleet/recompute PyLayer) replays
            # the forward under no_grad, so the gate.loss side-channel the
            # aux loss reads would be DETACHED — the router would silently
            # never learn. The compiled path is fine: use
            # ParallelEngine(remat=True), whose jax.checkpoint replays
            # differentiably.
            raise ValueError(
                "moe_num_experts > 0 with cfg.recompute=True detaches the "
                "load-balance aux loss in eager training; use "
                "ParallelEngine(remat=True) instead of cfg.recompute")
        dtype = None if cfg.dtype == "float32" else convert_dtype(cfg.dtype)

        def cast(layer):
            # initializers draw float32; converting each block as it is
            # built keeps the peak at the model's own dtype plus ONE f32
            # block — a whole f32 model (14 GB at Llama-3-8B widths x 16
            # layers) does not fit the chip its bf16 form is sized for
            if dtype is not None:
                layer._convert_dtype(dtype)
            return layer

        self.embed_tokens = cast(Embedding(cfg.vocab_size, cfg.hidden_size))
        self.embed_tokens.weight.pspec = P("tensor", None)
        self.layers = LayerList([cast(LlamaDecoderLayer(cfg, layer_idx=i))
                                 for i in range(cfg.num_hidden_layers)])
        self.norm = LlamaRMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        cos, sin = _rope_tables(head_dim, cfg.max_position_embeddings, cfg.rope_theta)
        self._cos = cos
        self._sin = sin
        if dtype is not None:
            self._convert_dtype(dtype)

    def forward(self, input_ids, caches=None, pos_offset=0):
        x = self.embed_tokens(input_ids)
        if self.cfg.sequence_parallel:
            x = shard_constraint(x, P("data", "sep", None))
        elif self.cfg.context_parallel:
            x = shard_constraint(x, P("data", "context", None))
        for i, layer in enumerate(self.layers):
            cache = caches[i] if caches is not None else None
            if self._should_recompute():
                from ..distributed.fleet.recompute import recompute

                x = recompute(lambda v, l=layer: l(v, self._cos, self._sin, cache,
                                                   pos_offset), x)
            else:
                x = layer(x, self._cos, self._sin, cache, pos_offset)
        return self.norm(x)

    def decode_step(self, token, caches, pos):
        """token: Tensor (B, 1) int; caches: list of (ck, cv) Tensors per
        layer; pos: traced int32 scalar. Returns (normed hidden, new caches)."""
        x = self.embed_tokens(token)
        new = []
        for layer, (ck, cv) in zip(self.layers, caches):
            x, ck, cv = layer.decode(x, self._cos, self._sin, ck, cv, pos)
            new.append((ck, cv))
        return self.norm(x), new

    def prefill(self, input_ids, caches):
        """Fill the decode caches from the whole prompt in one forward;
        returns (normed hidden for ALL prompt positions, new caches)."""
        x = self.embed_tokens(input_ids)
        new = []
        for layer, (ck, cv) in zip(self.layers, caches):
            x, ck, cv = layer.prefill(x, self._cos, self._sin, ck, cv)
            new.append((ck, cv))
        return self.norm(x), new

    def paged_decode_step(self, token, pools, block_tables, pos, lora=None):
        """Paged continuous-batching decode: like :meth:`decode_step` but
        K/V read/write goes through per-row block tables into the shared
        block pool. token: Tensor (B, 1); pools: list of per-layer pool
        tuples — ``(kp, vp)`` Tensors (num_blocks, bs, KV, D), or
        ``(kq, ks, vq, vs)`` for the int8 pool (kv_quant="int8");
        block_tables: traced int32 (B, M); pos: traced int32 [B]; ``lora``:
        None or a per-layer list of gathered per-row adapter factors
        (``inference.lora.AdapterPool.gather_rows``) — all static shapes,
        so the multi-adapter program is the single-adapter program."""
        x = self.embed_tokens(token)
        new = []
        for i, (layer, pool) in enumerate(zip(self.layers, pools)):
            x, pool = layer.paged_decode(x, self._cos, self._sin, pool,
                                         block_tables, pos,
                                         lora=None if lora is None else lora[i])
            new.append(pool)
        return self.norm(x), new

    def paged_verify_step(self, tokens, pools, block_tables, pos, lora=None):
        """Speculative verify: score a WINDOW of W = k+1 tokens per row in
        one program — :meth:`paged_decode_step` generalized from 1 to W
        positions (W = 1 is plain decode). tokens: Tensor (B, W) = current
        token followed by the k drafted tokens, at positions
        ``pos[b] + arange(W)``; pools/block_tables/pos as in
        :meth:`paged_decode_step`. Returns (normed hidden (B, W, hidden),
        new pools) — the caller projects to logits for all W positions and
        runs rejection sampling."""
        x = self.embed_tokens(tokens)
        new = []
        for i, (layer, pool) in enumerate(zip(self.layers, pools)):
            x, pool = layer.paged_verify(x, self._cos, self._sin, pool,
                                         block_tables, pos,
                                         lora=None if lora is None else lora[i])
            new.append(pool)
        return self.norm(x), new

    def paged_prefill_chunk(self, input_ids, pools, block_table, start,
                            lora=None, last_idx=None):
        """Stream ONE prompt chunk into the paged pool (chunked prefill:
        the same compiled program serves every chunk of every prompt
        length — no per-bucket compile family). input_ids: Tensor (1, C);
        start: traced int32 block-aligned chunk origin. Returns (normed
        hidden, new pools): the hidden of the whole chunk, or with
        ``last_idx`` (traced) of that one token, (1, 1, H)."""
        x = self.embed_tokens(input_ids)
        new = []
        for i, (layer, pool) in enumerate(zip(self.layers, pools)):
            x, pool = layer.paged_prefill_chunk(
                x, self._cos, self._sin, pool, block_table, start,
                lora=None if lora is None else lora[i])
            new.append(pool)
        h = self.norm(x)
        if last_idx is not None:
            h = Tensor(jax.lax.dynamic_slice_in_dim(h.value, last_idx, 1, 1))
        return h, new

    def paged_decode_chunk_step(self, input_ids, pools, block_tables, pos,
                                block_table, start, last_idx):
        """:meth:`paged_decode_step` and :meth:`paged_prefill_chunk` as ONE
        step, so that a tick which carries a prompt chunk reads every weight
        once and not twice: input_ids Tensor (1, B + C) — the B decode
        rows' tokens, then the chunk's C; embedding, norms, projections and
        MLP run on the joined rows, the attention of each layer splits
        (:func:`_rows_and_chunk_attention`). Returns (normed hidden
        (1, B + 1, hidden): the decode rows, then the chunk's token at
        ``last_idx`` — what the head is wanted for; new pools). Without
        ``lora``: a server with adapters keeps the two separate steps."""
        B = block_tables.shape[0]
        attend = _rows_and_chunk_attention(block_tables, pos, block_table,
                                           start)
        x = self.embed_tokens(input_ids)
        new = []
        for layer, pool in zip(self.layers, pools):
            x, pool = layer.paged_decode_chunk(x, self._cos, self._sin, pool,
                                               attend)
            new.append(pool)
        x = apply_op(lambda v: jnp.concatenate(
            [v[:, :B], jax.lax.dynamic_slice_in_dim(v, B + last_idx, 1, 1)],
            axis=1), x, op_name="decode_rows_and_last")
        return self.norm(x), new

    def _should_recompute(self):
        from ..framework.core import is_grad_enabled

        return self.cfg.recompute and self.training and is_grad_enabled()


class LlamaForCausalLM(Layer):
    def __init__(self, cfg: LlamaConfig):
        super().__init__()
        self.cfg = cfg
        self.model = LlamaModel(cfg)
        if not cfg.tie_word_embeddings:
            init = Normal(0.0, 0.02)
            self.lm_head = Linear(cfg.hidden_size, cfg.vocab_size, bias_attr=False,
                                  weight_attr=init)
            self.lm_head.weight.pspec = P(None, "tensor")
            if cfg.dtype != "float32":
                from ..framework.dtype import convert_dtype

                self.lm_head._convert_dtype(convert_dtype(cfg.dtype))
        if cfg.lora_rank:
            self.attach_lora(cfg.lora_rank, alpha=cfg.lora_alpha,
                             targets=cfg.lora_targets)

    def attach_lora(self, rank, alpha=None, targets=None):
        """Wrap the projection Linears with trainable LoRA factors
        (nn/lora.py); ``targets`` defaults to all of
        :data:`LLAMA_LORA_TARGETS`. Base weights freeze; only A/B train."""
        from ..nn.lora import attach_lora

        return attach_lora(self, rank, alpha=alpha,
                           targets=targets or LLAMA_LORA_TARGETS)

    def merge_lora(self, targets=None):
        """Fold trained adapter deltas into the base weights and restore
        plain Linears — the dense-equivalent export the serving exactness
        tests compare against."""
        from ..nn.lora import merge_lora

        return merge_lora(self, targets=targets or LLAMA_LORA_TARGETS)

    def _moe_aux(self):
        """Sum of the MoE gates' load-balance losses from the last forward
        (None for dense configs)."""
        total = None
        for layer in self.model.layers:
            aux = getattr(layer.mlp, "aux_loss", None)
            if aux is not None:
                total = aux if total is None else total + aux
        return total

    def forward(self, input_ids, labels=None):
        h = self.model(input_ids)
        if labels is not None and self.cfg.fused_lm_head_ce:
            from ..ops.fused_ce import fused_linear_cross_entropy

            tied = self.cfg.tie_word_embeddings
            w = self.model.embed_tokens.weight if tied else self.lm_head.weight
            from ..ops.fused_ce import capped_chunk_size

            chunk = capped_chunk_size(self.cfg.ce_chunk_size,
                                      input_ids.shape[1])
            loss = apply_op(
                lambda hv, wv, lv: fused_linear_cross_entropy(
                    hv, wv, lv, chunk_size=chunk, transpose_weight=tied),
                h, w, labels, op_name="fused_linear_cross_entropy")
            aux = self._moe_aux()
            if aux is not None:
                loss = loss + self.cfg.moe_aux_coeff * aux
            return loss
        if self.cfg.tie_word_embeddings:
            logits = apply_op(lambda v, w: jnp.matmul(v, w.T), h,
                              self.model.embed_tokens.weight)
        else:
            logits = self.lm_head(h)
        if labels is None:
            return logits
        loss = self.loss_fn(logits, labels)
        aux = self._moe_aux()
        if aux is not None:
            loss = loss + self.cfg.moe_aux_coeff * aux
        return loss

    def loss_fn(self, logits, labels):
        """Next-token CE with fp32 softmax (ParallelCrossEntropy math).

        MoE configs: the gates' load-balance aux loss (recorded by the
        forward that produced ``logits``) is folded in here too, so
        ``ParallelEngine(loss_fn=model.loss_fn)`` trains the router. A
        fully external loss_fn must add ``cfg.moe_aux_coeff *
        model._moe_aux()`` itself or the routing degenerates."""
        loss = F.cross_entropy(logits, labels, reduction="mean")
        aux = self._moe_aux()
        if aux is not None:
            loss = loss + self.cfg.moe_aux_coeff * aux
        return loss

    def quantize_int8(self):
        """Convert every projection (q/k/v/o, gate/up/down, lm_head) to
        weight-only int8 for decode (ref fused_multi_transformer_int8 /
        weight-only PTQ; TPU rationale in ops/int8.py: decode tokens/s is
        HBM-bound on parameter bytes, int8 halves them). Embedding stays in
        the model dtype (it is gathered, not matmul'd). In-place; returns
        self. Use for inference only — int8 weights do not train."""
        import os

        from ..nn.quant import Int8Linear
        from ..ops.int8 import quantize_per_channel

        fuse_qkv = os.environ.get("PT_W8_FUSED_QKV") == "1"
        for layer in self.model.layers:
            att, mlp = layer.self_attn, layer.mlp
            if isinstance(mlp, LlamaMoEMLP):
                # MoE experts stay in the model dtype: the stacked einsum
                # path has no per-expert int8 kernel yet (routing keeps the
                # active weight bytes at K/E of the dense equivalent anyway)
                mlp = None
            if fuse_qkv:
                # one [K, Nq+Nk+Nv] int8 weight (per-channel scales are
                # column-independent, so fused == separate numerically);
                # the bf16 projections are dropped from the module tree so
                # the decode weight stream isn't paid twice
                wcat = jnp.concatenate(
                    [att.q_proj.weight.value, att.k_proj.weight.value,
                     att.v_proj.weight.value], axis=1)
                w_q, sc = quantize_per_channel(wcat)
                att._w8_split = (int(att.q_proj.weight.shape[1]),
                                 int(att.k_proj.weight.shape[1]),
                                 int(att.v_proj.weight.shape[1]))
                att.qkv_fused = Int8Linear(w_q, sc)
                att.q_proj = att.k_proj = att.v_proj = None
                att.o_proj = Int8Linear.from_linear(att.o_proj)
            else:
                for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
                    setattr(att, name,
                            Int8Linear.from_linear(getattr(att, name)))
            if mlp is not None:
                for name in ("gate_proj", "up_proj", "down_proj"):
                    setattr(mlp, name,
                            Int8Linear.from_linear(getattr(mlp, name)))
        if not self.cfg.tie_word_embeddings:
            self.lm_head = Int8Linear.from_linear(self.lm_head)
        self._gen_cache = {}  # old compiled loops close over bf16 params
        return self

    def generate(self, input_ids, max_new_tokens: int = 32,
                 temperature: float = 0.0, top_k: int = 0,
                 top_p: float = 0.0, seed: int = 0,
                 eos_token_id: Optional[int] = None, num_beams: int = 1,
                 length_penalty: float = 0.0):
        """Autoregressive generation with a compiled single-token decode loop
        (PaddleNLP `model.generate` surface; greedy when temperature == 0).

        TPU-native design: fixed-size KV caches (B, P+N, KV, D) updated via
        dynamic_update_slice, one lax.scan over P+N-1 steps covering prefill
        and decode uniformly — the whole loop is ONE compiled program, no
        per-step dispatch and no dynamic shapes. Returns (B, P+N) int32 of
        prompt + generated tokens.
        """
        from ..framework.dtype import convert_dtype
        from ..jit import functional_call
        from .generation import compiled_cached_generate

        cfg = self.cfg
        kv = cfg.num_key_value_heads
        d = cfg.hidden_size // cfg.num_attention_heads
        cdtype = convert_dtype(cfg.dtype)
        model = self

        def make_caches(B, L):
            flat = []
            for _ in range(cfg.num_hidden_layers):
                flat += [jnp.zeros((B, L, kv, d), cdtype),
                         jnp.zeros((B, L, kv, d), cdtype)]
            return flat

        def head(h):
            if cfg.tie_word_embeddings:
                return apply_op(lambda v, w: jnp.matmul(v, w.T), h,
                                model.model.embed_tokens.weight)
            return model.lm_head(h)

        def run_one(p, tok, flat_caches, pos):
            caches = [(Tensor(flat_caches[2 * i]), Tensor(flat_caches[2 * i + 1]))
                      for i in range(cfg.num_hidden_layers)]

            def call():
                h, new = model.model.decode_step(Tensor(tok), caches, pos)
                return head(h), new

            logits, new = functional_call(model, p, call_fn=lambda: call())
            flat = []
            for ck, cv in new:
                flat += [ck.value, cv.value]
            return logits.value[:, 0], flat

        def prefill_fn(p, prompt, flat_caches):
            caches = [(Tensor(flat_caches[2 * i]), Tensor(flat_caches[2 * i + 1]))
                      for i in range(cfg.num_hidden_layers)]

            def call():
                h, new = model.model.prefill(Tensor(prompt), caches)
                return head(h[:, -1:]), new  # logits only for the last token

            logits, new = functional_call(model, p, call_fn=call)
            flat = []
            for ck, cv in new:
                flat += [ck.value, cv.value]
            return logits.value[:, 0], flat

        if num_beams > 1:
            if temperature or top_k:
                import warnings

                warnings.warn(
                    "num_beams > 1 uses deterministic beam search; "
                    "temperature/top_k/seed are ignored", UserWarning)
            from .generation import compiled_beam_search

            return compiled_beam_search(
                self, input_ids, num_beams=num_beams,
                max_new_tokens=max_new_tokens, eos_token_id=eos_token_id,
                length_penalty=length_penalty, make_caches=make_caches,
                run_one=run_one, prefill=prefill_fn,
                max_positions=cfg.max_position_embeddings)
        return compiled_cached_generate(
            self, input_ids, max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p, seed=seed,
            eos_token_id=eos_token_id, make_caches=make_caches,
            run_one=run_one, prefill=prefill_fn,
            max_positions=cfg.max_position_embeddings)


def llama_pretrain_loss(model: LlamaForCausalLM, input_ids, labels):
    return model(input_ids, labels)
