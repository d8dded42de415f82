"""Pallas TPU kernel: attention over a paged latent pool (ops/
latent_attention.py has the mathematics and the jnp reference).

The walk is ``paged_attention_pallas.py``'s (PR 26): one program per batch
row (x a tile of its query rows), the block table and the positions as
scalar-prefetch operands, the pool whole in HBM, and each program copies its
own row's LIVE blocks only, in groups of ``G`` whole ``(bs, row_width)``
blocks into a two-slot VMEM buffer, group ``i + 1`` in flight while group
``i`` meets ONE online-softmax update. What differs: there is one pool and
one copy a block — the tile that landed is the key of every head and, in its
first ``v_width`` lanes, the value — all ``H`` heads of a token are rows of
one matmul against it, and the scores carry the position-dependent query
scale, computed in the kernel from each row's position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# tokens per group: a row of 384 lanes is 768 B, so two slots of 1024 tokens
# are 1.5 MB of VMEM beside a chunk tile's (256, 1024) float32 scores.
# Measured on the v5e (tools/expert_bench.py, PR 33: 128 decode rows at
# contexts of ~3.7k, share of the memory roofline at 640 B a position): 21.3 /
# 23.8 % at 512 / 1024 tokens with blocks of 16 tokens, 38.6 / 47.2 % with
# blocks of 64 — the walk issues one DMA descriptor a block, and a 16-token
# block of this pool is 12 KB, a third of a K/V pool's
_GROUP_TOKENS = 1024
# query rows (token, head) per program of a prompt chunk
_Q_ROWS = 256


def _interpret() -> bool:
    from .select import pallas_interpret

    return pallas_interpret()


def _kernel(tbl_ref, pos_ref, q_ref, pool_hbm, o_ref, buf, sem, m_ref, l_ref,
            acc_ref, *, bs, H, M, G, R, Dv, scale, beta, orig):
    T = G * bs
    b = pl.program_id(0)
    row0 = pl.program_id(1) * R          # first (token, head) row of the tile
    pos = pos_ref[b]
    # live blocks: through the causal frontier of the tile's LAST token;
    # table entries past it (scratch, stale ids) are never read
    nblk = jnp.minimum((pos + (row0 + R - 1) // H) // bs + 1, M)
    ngroups = (nblk + G - 1) // G

    def dma(i, slot, start):
        live = jnp.clip(nblk - i * G, 0, G)

        def one(j, carry):
            cp = pltpu.make_async_copy(
                pool_hbm.at[tbl_ref[b, i * G + j]],
                buf.at[slot, pl.ds(j * bs, bs)], sem.at[slot])
            if start:
                cp.start()
            else:
                cp.wait()
            return carry

        jax.lax.fori_loop(0, live, one, 0)
        if start:
            # a block past the frontier is not fetched; the tile is also the
            # VALUE, so stale bits must not meet a zero probability
            def zero(j, carry):
                dst = buf.at[slot, pl.ds(j * bs, bs)]
                dst[...] = jnp.zeros(dst.shape, buf.dtype)
                return carry

            jax.lax.fori_loop(live, G, zero, 0)

    ri = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    row_qpos = pos + (row0 + ri) // H                       # (R, 1)
    sc = jnp.float32(scale) * (1.0 + jnp.float32(beta) * jnp.log(
        1.0 + jnp.floor(row_qpos.astype(jnp.float32) / jnp.float32(orig))))

    def compute(i, slot):
        tile = buf[slot]                                    # (T, row_width)
        s = jax.lax.dot_general(q_ref[0], tile, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sc
        s = jnp.where(i * T + ci <= row_qpos, s, NEG_INF)
        m_prev, l_prev = m_ref[:, :1], l_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True), (R, 128))
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(tile.dtype), tile[:, :Dv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, (R, 128))

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    dma(0, 0, True)

    def body(i, carry):
        slot = i % 2

        @pl.when(i + 1 < ngroups)
        def _prefetch():
            dma(i + 1, 1 - slot, True)

        dma(i, slot, False)
        compute(i, slot)
        return carry

    jax.lax.fori_loop(0, ngroups, body, 0)
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
                ).astype(o_ref.dtype)


def latent_attention(q, pool, block_tables, pos, *, v_width, scale,
                     qscale=(0.0, 1)):
    """See ``ops.latent_attention.latent_attention``. q (B, W, H, D); pool
    (N, bs, D); returns (B, W, H, v_width)."""
    B, W, H, D = q.shape
    N, bs, _ = pool.shape
    M = block_tables.shape[1]
    if not _interpret() and (D % 128 or v_width % 128 or bs % 8):
        # select.select_latent_attention keeps these shapes off the kernel
        raise ValueError(f"latent attention kernel needs whole lane tiles "
                         f"and block_size % 8 == 0, got row {D}, value "
                         f"{v_width}, block {bs}")
    # tokens per program: all of a decode row's one; of a chunk, as many
    # whole tokens as fit _Q_ROWS rows
    tpt = max(1, min(W, _Q_ROWS // H))
    while W % tpt:
        tpt -= 1
    R, NQ = tpt * H, W // tpt
    G = max(1, min(_GROUP_TOKENS // bs, M))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NQ),
        in_specs=[pl.BlockSpec((1, R, D), lambda b, t, *_: (b, t, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, R, v_width), lambda b, t, *_: (b, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, G * bs, D), pool.dtype),       # group slots
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((R, 128), jnp.float32),            # running max
            pltpu.VMEM((R, 128), jnp.float32),            # running sum
            pltpu.VMEM((R, v_width), jnp.float32),        # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, H=H, M=M, G=G, R=R, Dv=v_width,
                          scale=float(scale), beta=float(qscale[0]),
                          orig=float(qscale[1])),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, W * H, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(),
        name="latent_attend",
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
      q.reshape(B, W * H, D), pool)
    return out.reshape(B, W, H, v_width)
