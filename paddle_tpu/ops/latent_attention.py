"""Attention over a paged LATENT pool — MLA in its absorbed form.

A latent layer caches ONE row a position: ``[c | k_r | 0]``, the normed
latent (``v_width`` values), the one rotated rope key all heads share, and
zero pad up to whole 128-lane tiles — ``pool`` is ``(num_blocks, block_size,
row_width)`` (inference/cache_spec.py, ``latent``). With the key
up-projection absorbed into the query (``q~_i = W_uk,i^T q_nope,i``) every
head's key IS that row and its value the row's first ``v_width`` lanes:

    s_i(t, u) = (q_i(t) . row_u) * scale * a_t      causal, softmax in f32
    o_i(t)    = sum_u p_i(t, u) row_u[:v_width]

where ``q_i(t) = [q~_i | q_rope,i | 0]`` is laid out like a row and ``a_t = 1
+ beta * ln(1 + floor(t / orig))`` is the position-dependent query scale
(``qscale = (beta, orig)``; ``beta`` 0 turns it off). All heads share the one
row, so a position costs ``row_width`` values of traffic however many heads
there are: ``2 * H * (width + v_width)`` FLOPs over ``2 * width`` bytes.

:func:`latent_attention` serves decode (``W`` = 1, a row per slot) and the
prompt chunk (``B`` = 1, ``W`` = C) alike, as ``paged_verify_attention`` does
for K/V pools. Which implementation runs is decided by
``select.select_latent_attention``: the Pallas kernel
(``latent_attention_pallas.py``) or the jnp composition below, which gathers
the whole table's context and is the reference the kernel is tested against.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def query_scale(qpos, qscale):
    """``a_t`` of the positions ``qpos`` (int), float32."""
    beta, orig = qscale
    return 1.0 + beta * jnp.log(
        1.0 + jnp.floor(qpos.astype(jnp.float32) / orig))


def write_latent_rows(pool, rows, block_tables, pos):
    """One new row per slot: ``pool[table[b, pos // bs], pos % bs] =
    rows[b]``. rows (B, row_width). Slots parked on the scratch block (a
    zeroed table row) overwrite scratch."""
    bs = pool.shape[1]
    bid = jnp.take_along_axis(block_tables, (pos // bs)[:, None], axis=1)[:, 0]
    return pool.at[bid, pos % bs].set(rows.astype(pool.dtype))


def write_latent_chunk(pool, rows, block_table, start):
    """A prompt chunk's rows (C, row_width), C a multiple of the block
    size, into the consecutive table entries from ``start // bs`` on
    (``start`` traced, block-aligned): one blocked scatter."""
    bs = pool.shape[1]
    nb = rows.shape[0] // bs
    blocks = jax.lax.dynamic_slice_in_dim(block_table, start // bs, nb, 0)
    return pool.at[blocks].set(
        rows.reshape(nb, bs, rows.shape[-1]).astype(pool.dtype))


def _reference(q, pool, block_tables, pos, v_width, scale, qscale):
    B, W, H, D = q.shape
    ctx = pool[block_tables].reshape(B, -1, D)              # (B, L, D)
    qpos = pos[:, None] + jnp.arange(W)[None, :]            # (B, W)
    s = jnp.einsum("bwhd,bld->bhwl", q, ctx).astype(jnp.float32)
    s = s * (scale * query_scale(qpos, qscale))[:, None, :, None]
    mask = jnp.arange(ctx.shape[1])[None, None, :] <= qpos[:, :, None]
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, -1).astype(q.dtype)
    return jnp.einsum("bhwl,bld->bwhd", p, ctx[..., :v_width])


def latent_attention(q, pool, block_tables, pos, *, v_width, scale,
                     qscale=(0.0, 1)):
    """q (B, W, H, row_width) at positions ``pos[b] + arange(W)``, laid out
    like a pool row; pool (N, bs, row_width); block_tables (B, M); pos
    int32 (B,). The rows of the window must already be in the pool.
    Returns (B, W, H, v_width)."""
    from .select import XLA, record, select_latent_attention

    if record("latent_attention", select_latent_attention(
            q.shape, pool.shape, v_width)) == XLA:
        return _reference(q, pool, block_tables, pos, v_width, scale, qscale)
    from . import latent_attention_pallas as lk

    return lk.latent_attention(q, pool, block_tables, pos, v_width=v_width,
                               scale=scale, qscale=qscale)
