"""Attention ops.

Ref: paddle/fluid/operators/fused/fused_attention_op.cu + fmha_ref.h — rebuilt
as a single jnp composition (XLA fuses) with an optional Pallas
flash-attention fast path (paddle_tpu.ops.flash_attention) used automatically
on TPU for long sequences.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ...framework.core import Tensor
from ...framework.dispatch import apply_op
from ...framework.flags import GLOBAL_FLAGS


def _sdpa_ref(q, k, v, mask=None, dropout_p=0.0, is_causal=False, scale=None):
    """q,k,v: (B, S, H, D) paddle convention."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / math.sqrt(d)
    qh = jnp.swapaxes(q, 1, 2)  # B,H,S,D
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhsd,bhtd->bhst", qh, kh).astype(jnp.float32) * s
    if is_causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        cm = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(cm, logits, -1e30)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            logits = jnp.where(mask, logits, -1e30)
        else:
            logits = logits + mask.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
    return jnp.swapaxes(out, 1, 2)  # B,S,H,D


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, scale=None, name=None):
    """Inputs (B, S, H, D). Routes unmasked, dropout-free, 128-aligned
    calls through ``ops.flash_attention`` (Pallas on a TPU), everything
    else through the XLA reference path."""
    # the flash entry point selects kernel vs jnp composition itself
    # (ops/select.py); here only the cases it cannot express are excluded
    if (GLOBAL_FLAGS.get("use_pallas_kernels") and attn_mask is None
            and dropout_p == 0.0 and query.shape[1] % 128 == 0
            and key.shape[1] % 128 == 0 and query.shape[-1] >= 64):
        from ...ops.flash_attention import flash_attention_bshd

        return apply_op(
            lambda q, k, v: flash_attention_bshd(q, k, v, causal=is_causal,
                                                 scale=scale),
            query, key, value, op_name="flash_attention")
    args = [query, key, value]
    if attn_mask is not None:
        return apply_op(
            lambda q, k, v, m: _sdpa_ref(q, k, v, m, dropout_p, is_causal, scale),
            query, key, value, attn_mask, op_name="sdpa")
    return apply_op(lambda q, k, v: _sdpa_ref(q, k, v, None, dropout_p, is_causal, scale),
                    query, key, value, op_name="sdpa")
