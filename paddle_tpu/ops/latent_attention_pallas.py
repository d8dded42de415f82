"""Pallas TPU kernel: attention over a paged latent pool (ops/
latent_attention.py has the mathematics and the jnp reference).

One program per batch row (x a tile of its query rows), the block table and
the positions as scalar-prefetch operands, the pool whole in HBM; a program
meets its row's LIVE blocks only, in groups of ``G`` whole ``(bs,
row_width)`` blocks, ONE online-softmax update a group. There is one pool
and one copy a block — the tile that landed is the key of every head and, in
its first ``v_width`` lanes, the value — all ``H`` heads of a token are rows
of one matmul against it, and the scores carry the position-dependent query
scale, computed in the kernel from each row's position.

The copies of a CALL are one double-buffered stream over all its (program,
group) pairs (PR 34), not a stream a row that starts cold and drains empty:
the grid is walked in order (``arbitrary``), and while a program computes its
last group the first group of the NEXT program — the next row, or the next
tile of a chunk — is already in flight, its slot handed over in SMEM. Only
the call's first group is fetched cold, the last program starts nothing, and
a row ahead is read through ITS OWN frontier. A whole group's ``G`` copies
are started without a scalar loop and waited for with one wait for the
slot's byte count; the loop with a dynamic count serves a row's last,
partial group alone.
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
# Measured on the v5e (tools/expert_bench.py --ops latent, PR 34: 128 decode
# rows, blocks of 64, mean contexts 1.6k / 2.6k / 3.2k / 3.7k, us a row): 3.08 /
# 4.61 / 5.58 / 6.30 at 512 tokens, 2.50 / 3.63 / 4.29 / 4.87 at 1024 (50.1 / 56.0 /
# 58.3 / 59.4 % of the memory roofline at 640 B a position; one stream a ROW
# read 3.41 / 4.69 / 5.48 / 6.13). A smaller group pays the softmax chain's
# fixed cost more often (compute alone: 3.61 against 2.53 at 2.6k); 2048 reads
# 2.59 / 3.52 / 4.09 at 1.6k / 2.6k / 3.2k for 3.1 MB of slots. A third and a
# fourth slot change nothing (3.63 / 3.71 against 3.72 at 2.6k): what is left
# beside the arithmetic is the core's own issue of one descriptor a block.
# Blocks of 16 tokens (12 KB a copy) read 21-24 % before PR 34 (PR 33)
_GROUP_TOKENS = 1024
# query rows (token, head) per program of a prompt chunk
_Q_ROWS = 256
# a whole group's copy starts are unrolled this many at a time
_UNROLL = 16


def _interpret() -> bool:
    from .select import pallas_interpret

    return pallas_interpret()


def _dma(src, dst, sem, start):
    """Start a copy or wait for one. (This and :func:`_attend_group` are
    module-level so that tools/expert_bench.py can time the kernel's copies
    and its arithmetic apart.)"""
    cp = pltpu.make_async_copy(src, dst, sem)
    if start:
        cp.start()
    else:
        cp.wait()


def _attend_group(q, tile, sc, live, m_ref, l_ref, acc_ref, Dv):
    """ONE online-softmax update of the (R, .) statistics with a group's
    tile (T, row_width); ``live`` (R, T) is the causal mask."""
    R = q.shape[0]
    s = jax.lax.dot_general(q, tile, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sc
    s = jnp.where(live, s, NEG_INF)
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


def _kernel(tbl_ref, pos_ref, q_ref, pool_hbm, o_ref, buf, sem, slot_ref,
            m_ref, l_ref, acc_ref, *, bs, H, M, G, R, B, NQ, Dv, scale, beta,
            orig):
    T = G * bs
    b, t = pl.program_id(0), pl.program_id(1)
    k = b * NQ + t              # the grid is walked in this order, one by one

    def live_blocks(k):
        # of program k: through the causal frontier of its tile's LAST
        # token; table entries past it (scratch, stale ids) are never read
        return jnp.minimum(
            (pos_ref[k // NQ] + ((k % NQ + 1) * R - 1) // H) // bs + 1, M)

    nblk = live_blocks(k)
    ngroups = (nblk + G - 1) // G
    # the program after this one (the last has none: it starts nothing)
    k_nxt = jnp.minimum(k + 1, B * NQ - 1)
    nblk_nxt = live_blocks(k_nxt)

    def copies(k, nblk, i, slot, start):
        """Start, or wait for, the copies of group ``i`` of program ``k`` (it
        has ``nblk`` live blocks) in ``slot``."""
        row = k // NQ
        live = nblk - i * G

        def one(j, carry=0):
            _dma(pool_hbm.at[tbl_ref[row, i * G + j]],
                 buf.at[slot, pl.ds(j * bs, bs)], sem.at[slot], start)
            return carry

        def unrolled(c, carry):
            for u in range(_UNROLL):
                one(c * _UNROLL + u)
            return carry

        @pl.when(live >= G)
        def _whole():
            if not start:
                # the slot's G copies signal one semaphore: ONE wait for
                # the slot's byte count
                _dma(buf.at[slot], buf.at[slot], sem.at[slot], False)
            elif G <= _UNROLL:
                for j in range(G):              # static: no scalar loop
                    one(j)
            else:
                jax.lax.fori_loop(0, G // _UNROLL, unrolled, 0)
                for j in range(G // _UNROLL * _UNROLL, G):
                    one(j)

        @pl.when(live < G)
        def _partial():
            # a row's last group: the blocks past the frontier are not
            # fetched; what the slot holds there is an older group's rows
            # (or the zeros below), finite under a zero probability
            jax.lax.fori_loop(0, live, one, 0)

    ri = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)
    row_qpos = pos_ref[b] + (t * R + ri) // H               # (R, 1)
    sc = jnp.float32(scale) * (1.0 + jnp.float32(beta) * jnp.log(
        1.0 + jnp.floor(row_qpos.astype(jnp.float32) / jnp.float32(orig))))

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(k == 0)
    def _cold():
        # the call's only cold fetch. The tile is also the VALUE, so the
        # bits a slot starts with must not meet a zero probability
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        copies(k, nblk, 0, 0, True)

    # the slot of this program's first group: the program before started it
    slot0 = jnp.where(k == 0, 0, slot_ref[0])

    def body(i, carry):
        slot = (slot0 + i) % 2
        own = i + 1 < ngroups

        # the next pair of the call's ONE stream of (program, group)s: this
        # row's next group or, during its last, the next program's first
        @pl.when(own | (k + 1 < B * NQ))
        def _prefetch():
            copies(jnp.where(own, k, k_nxt), jnp.where(own, nblk, nblk_nxt),
                   jnp.where(own, i + 1, 0), 1 - slot, True)

        copies(k, nblk, i, slot, False)
        _attend_group(q_ref[0], buf[slot], sc, i * T + ci <= row_qpos,
                      m_ref, l_ref, acc_ref, Dv)
        return carry

    jax.lax.fori_loop(0, ngroups, body, 0)
    slot_ref[0] = (slot0 + ngroups) % 2
    o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[:, :1], 1e-30)
                ).astype(o_ref.dtype)


def latent_attention(q, pool, block_tables, pos, *, v_width, scale,
                     qscale=(0.0, 1)):
    """See ``ops.latent_attention.latent_attention``. q (B, W, H, D); pool
    (N, bs, D); returns (B, W, H, v_width)."""
    D, bs = q.shape[-1], pool.shape[1]
    if not _interpret() and (D % 128 or v_width % 128 or bs % 8):
        # select.select_latent_attention keeps these shapes off the kernel
        raise ValueError(f"latent attention kernel needs whole lane tiles "
                         f"and block_size % 8 == 0, got row {D}, value "
                         f"{v_width}, block {bs}")
    return _latent_attend(q, pool, block_tables, pos, v_width=v_width,
                          scale=float(scale), beta=float(qscale[0]),
                          orig=float(qscale[1]), group_tokens=_GROUP_TOKENS,
                          interpret=_interpret())


# jitted, with everything the trace reads as a static argument: every layer
# of a program calls this with the same shapes, and jit then traces and
# lowers the kernel once a shape instead of once a call site (a site costs
# tenths of a second on the chip's host — 18 sites in the serving cell's two
# programs, +13 s of set-up measured before this, PR 34)
@functools.partial(jax.jit, static_argnames=(
    "v_width", "scale", "beta", "orig", "group_tokens", "interpret"))
def _latent_attend(q, pool, block_tables, pos, *, v_width, scale, beta, orig,
                   group_tokens, interpret):
    B, W, H, D = q.shape
    N, bs, _ = pool.shape
    M = block_tables.shape[1]
    # tokens per program: all of a decode row's one; of a chunk, as many
    # whole tokens as fit _Q_ROWS rows
    tpt = max(1, min(W, _Q_ROWS // H))
    while W % tpt:
        tpt -= 1
    R, NQ = tpt * H, W // tpt
    G = max(1, min(group_tokens // bs, M))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NQ),
        in_specs=[pl.BlockSpec((1, R, D), lambda b, t, *_: (b, t, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, R, v_width), lambda b, t, *_: (b, t, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, G * bs, D), pool.dtype),       # group slots
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),      # the next program's first slot
            pltpu.VMEM((R, 128), jnp.float32),            # running max
            pltpu.VMEM((R, 128), jnp.float32),            # running sum
            pltpu.VMEM((R, v_width), jnp.float32),        # accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, H=H, M=M, G=G, R=R, B=B, NQ=NQ,
                          Dv=v_width, scale=scale, beta=beta, orig=orig),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, W * H, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="latent_attend",
    )(block_tables.astype(jnp.int32), pos.astype(jnp.int32),
      q.reshape(B, W * H, D), pool)
    return out.reshape(B, W, H, v_width)
