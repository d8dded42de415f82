"""Pallas TPU kernels for the paged serving hot path.

One flash-style online-softmax kernel serves decode (W=1), speculative
verify (W=tick_window), and chunked prefill (B=1, W=chunk). The grid is
one program per batch row (x an optional q-row tile); the KV axis is NOT
on the grid. The block tables and the positions are the two
scalar-prefetch operands, the pools stay whole in HBM, and each program
loops over its own row's LIVE blocks only — ``(pos[b] + W - 1) // bs +
1`` of them, a bound read from the positions, so the work of a call
follows the tokens attended and not ``max_batch * max_len`` — in groups
of ``G`` blocks: ``G`` whole ``(bs, KV, D)`` copies of K and of V
(``make_async_copy`` of the block ``tbl[b, i*G + j]`` names) land in a
two-slot VMEM buffer, group ``i + 1`` in flight while group ``i`` is
consumed by ONE online-softmax update. The dense ``gather_block_kv`` copy
of the context never materializes in HBM, table entries past a row's
causal frontier (scratch block 0, stale ids) are never read, and there is
still one decode program and one chunk program whatever the lengths.

:func:`group_plan` decides ``G`` and the body at trace time from the
shapes. When all of a program's query rows fit one MXU pass (decode,
small verify windows) the group tile is consumed as it landed — reshaped
to ``(G*bs*KV, D)`` against ALL kv heads' query rows with a block-diagonal
mask: 8x the FLOPs of a memory-bound kernel, no per-head strided read of
the ``(G*bs, KV, D)`` tile. A prefill chunk (hundreds of rows a head,
compute bound) walks the kv heads instead. Running max/sum/accumulator
live in VMEM scratch across the loop. The int8 twin copies the code pool
directly and applies the per-(block, kv-head) scales on the VMEM tile —
k-scale on the fp32 QK accumulator, v-scale folded into the probabilities
before PV — so a dequantized pool is never built; its scales arrive as
per-row ``(B, M, KV)`` tables gathered through the block table.
``fused_lora_matmul`` fuses the per-slot BGMV adapter delta (gathered
A/B/scale factors) into the base projection matmul, one program per
(output tile, batch row).

The kernel's SCHEDULE is parameterized by
:class:`~paddle_tpu.autotune.kernel_geometry.PagedAttentionGeometry`
(and the LoRA kernel's by :class:`~paddle_tpu.autotune.kernel_geometry
.LoRAGeometry`): q-row tiling (extra parallel axis over the W*rep GQA
rows) and int8 cast placement are schedule-only — bit-exact against the
default. ``kv_block_depth`` overrides ``G``; that moves the
online-softmax update boundaries, so two depths agree to ~1e-6 and in
every greedy token, not bitwise, and the geometry sweep never varies it.
``geometry=`` is a trace-time parameter; when omitted, the process-wide
winner cache (``autotune.kernel_geometry.install_geometry_cache``) is
consulted at trace time, same contract as ``ops.set_kernel_mode``.

The jnp compositions in ``ops/paged_attention.py`` remain the bit-exact
references; which of the two runs is decided by the rules in
``ops/select.py`` (a TPU, ``PT_FLASH_INTERPRET=1``, or ``set_kernel_mode``;
shapes Mosaic refuses stay on jnp). The online softmax is
numerically equivalent but not bit-identical to the reference's two-pass
softmax (~1e-6 relative); greedy decode tokens are identical, which is
what the serving tests pin.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _interpret() -> bool:
    from .select import pallas_interpret

    return pallas_interpret()


def _resolve(op: str, dtype: str, key: int):
    from ..autotune.kernel_geometry import resolve_geometry

    return resolve_geometry(op, dtype, key)[0]


# ------------------------------------------------------------------ attention
# Tokens per group. Measured on the v5e at the serving cells' shapes
# (tools/kernel_bench.py --ops ragged, PR 26): 64 ragged decode rows read
# 41 / 56 / 62 / 60 % of the memory roofline at 64 / 128 / 256 / 512, and
# a 128-token chunk 10 / 18 / 25 % of the compute roofline at 64 / 128 /
# 256 (512 does not fit VMEM beside the chunk's accumulator).
_GROUP_TOKENS = 256


def group_plan(rows, KV, bs, D, M, kv_itemsize, depth=0):
    """(G, all_heads): blocks fetched and consumed per online-softmax
    update, and which body consumes them — decided at trace time from the
    shapes. ``rows`` is one program's q rows per kv head (W*rep, or the
    q-row tile).

    All kv heads at once when every q row of the program fits ONE MXU
    pass (KV*rows <= 128): the pass streams < 128 rows either way, so the
    block-diagonal waste is free and the group tile is consumed as it
    landed, with no per-head strided read (2.1x the per-head body at 64
    decode rows on the v5e). Past that (a prefill chunk: hundreds of rows
    a head) the 8x FLOPs are not free and each head runs its own matmul.

    G aims the group at ``_GROUP_TOKENS`` tokens, inside the table width
    and half the VMEM block budget (two slots of K and of V plus the f32
    score/probability temporaries; the other half is the q/out blocks and
    the accumulator, which ``select_paged_attention`` bounds). ``depth`` >
    0 (``PagedAttentionGeometry.kv_block_depth``) overrides G."""
    from .select import _VMEM_BLOCK_BUDGET

    all_heads = KV > 1 and KV * rows <= 128
    if depth > 0:
        return min(depth, M), all_heads
    qrows, cols = (KV * rows, KV) if all_heads else (rows, 1)

    def vmem(g):
        t = g * bs
        return 4 * t * KV * D * kv_itemsize + 3 * qrows * t * cols * 4

    G = max(1, min(_GROUP_TOKENS // bs, M))
    while G > 1 and vmem(G) > _VMEM_BLOCK_BUDGET // 2:
        G //= 2
    return G, all_heads


def _attn_kernel(tbl_ref, pos_ref, q_ref, k_hbm, v_hbm, *rest, bs, rep, KV,
                 M, G, R, quantized, early, all_heads, tiled, window=0,
                 ring=0, head_major=False):
    if quantized:
        ks_ref, vs_ref = rest[:2]
        rest = rest[2:]
    o_ref, kbuf, vbuf, sem, m_ref, l_ref, acc_ref = rest
    D = q_ref.shape[-1]
    T = G * bs
    b = pl.program_id(0)
    # first global q row of this program's tile (0 unless q_rows tiles
    # the W*rep group across its own grid axis)
    row0 = pl.program_id(1) * R if tiled else 0
    pos = pos_ref[b]
    # live blocks: through the causal frontier of the tile's LAST q row.
    # Table entries past it (scratch block 0, stale ids) are never read.
    last = (pos + (row0 + R - 1) // rep) // bs + 1
    nblk = last if ring else jnp.minimum(last, M)
    # a sliding window starts the walk at the block that holds the FIRST q
    # row's oldest visible position; ``ring`` > 0 says the table is a ring
    # of that many blocks (logical block j lives in entry j % ring)
    blk0 = (jnp.maximum(pos + row0 // rep - (window - 1), 0) // bs
            if window else 0)
    ngroups = (nblk - blk0 + G - 1) // G

    def block_of(buf, slot, j):
        """Where block ``j`` of a group lands in a slot of the VMEM buffer:
        ``(2, G*bs, KV, D)`` token-major, ``(2, G, KV, bs, D)`` head-major
        (the pool's own block layout either way: one contiguous copy)."""
        return (buf.at[slot, j] if head_major
                else buf.at[slot, pl.ds(j * bs, bs)])

    def dma(i, slot, start):
        """Start (or wait for) group i's whole-block copies into ``slot``.
        A block past the frontier is not fetched; its V slab is zeroed so
        a zero probability never meets stale non-finite bits. (Loops, not
        an unrolled ``pl.when`` a block: the trace stays the size of one
        block whatever G is.)"""
        live = jnp.clip(nblk - blk0 - i * G, 0, G)

        def one(j, carry):
            e = blk0 + i * G + j
            blk = tbl_ref[b, e % ring if ring else e]
            for s, (hbm, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                cp = pltpu.make_async_copy(
                    hbm.at[blk], block_of(buf, slot, j), sem.at[s, slot])
                if start:
                    cp.start()
                else:
                    cp.wait()
            return carry

        jax.lax.fori_loop(0, live, one, 0)
        if start:
            def zero(j, carry):
                dst = block_of(vbuf, slot, j)
                dst[...] = jnp.zeros(dst.shape, vbuf.dtype)
                return carry

            jax.lax.fori_loop(live, G, zero, 0)

    # loop-invariant index vectors: row-derived as (rows, 1), column-
    # derived as (1, cols) — only the compares run at (rows, cols)
    NG, rows, _ = acc_ref.shape       # (1, KV*R) all heads, (KV, R) per head
    C = T * KV if all_heads else T
    ri = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    ci = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    if all_heads:
        # rows are (kv head, q row), columns (token, kv head): a column
        # belongs to a row's softmax only on the block diagonal
        row_qpos = pos + (row0 + ri % R) // rep
        if head_major:
            # the tile landed (block, kv head, token in block)
            col_tok = ci // (KV * bs) * bs + ci % bs
            col_head = ci // bs % KV
        else:
            col_tok, col_head = ci // KV, ci % KV
        diag = col_head == ri // R
    else:
        row_qpos = pos + (row0 + ri) // rep
        col_tok, diag = ci, None
    if quantized:
        gi = jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)
        col_blk = col_tok // bs == gi            # (G, C) block one-hot

    def col_scales(sg):
        """(G, KV) per-block scales -> per kv head, the (1, C) scale of
        every column (exact: one-hot selects)."""
        def pick(g):
            return jnp.sum(jnp.where(col_blk, sg[:, g:g + 1], 0.0), axis=0,
                           keepdims=True)
        if not all_heads:
            return [pick(g) for g in range(KV)]
        out = jnp.zeros((1, C), jnp.float32)
        for g in range(KV):
            out = jnp.where(col_head == g, pick(g), out)
        return [out]

    def compute(i, slot):
        base = (blk0 + i * G) * bs
        mask = base + col_tok <= row_qpos
        if window:
            mask = jnp.logical_and(mask,
                                   base + col_tok > row_qpos - window)
        if all_heads:
            mask = jnp.logical_and(mask, diag)
        if quantized:
            off = pl.multiple_of(i * G, G)
            ksc = col_scales(ks_ref[0, pl.ds(off, G), :])
            # blocks past the frontier carry whatever scale their stale
            # table entry points at: keep it off the zero probabilities
            vsc = col_scales(jnp.where(i * G + gi < nblk,
                                       vs_ref[0, pl.ds(off, G), :], 0.0))
        if not head_major or all_heads:
            kt, vt = kbuf[slot], vbuf[slot]   # (T, KV, D) | (G, KV, bs, D)
        if quantized and (early or all_heads):
            # the int8->fp cast is exact wherever it sits; "early" casts
            # the whole group tile once, "scores" each head's slice
            kt, vt = kt.astype(q_ref.dtype), vt.astype(q_ref.dtype)
        for g in range(NG):
            if all_heads:
                q = q_ref[0].reshape(rows, D)
                k, v = kt.reshape(C, D), vt.reshape(C, D)
            else:
                q = q_ref[0, g]                           # (R, D)
                if head_major:
                    # a head's tokens are whole (bs, D) tiles of the group
                    k = kbuf[slot, :, g].reshape(T, D)
                    v = vbuf[slot, :, g].reshape(T, D)
                else:
                    k, v = kt[:, g, :], vt[:, g, :]       # (T, D)
                if quantized and not early:
                    k, v = k.astype(q.dtype), v.astype(q.dtype)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            if quantized:
                # reference order: scores * k_scale, then / sqrt(D)
                s = s * ksc[g]
            s = s / jnp.float32(math.sqrt(D))
            s = jnp.where(mask, s, NEG_INF)
            m_prev = m_ref[g][:, :1]
            l_prev = l_ref[g][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[g] = jnp.broadcast_to(
                l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True),
                (rows, 128))
            if quantized:
                p = p * vsc[g]                    # v scale into the probs
            acc_ref[g] = acc_ref[g] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = jnp.broadcast_to(m_new, (rows, 128))

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
    for g in range(NG):
        l_safe = jnp.maximum(l_ref[g][:, :1], 1e-30)
        out = (acc_ref[g] / l_safe).astype(o_ref.dtype)
        if all_heads:
            o_ref[0] = out.reshape(KV, R, D)
        else:
            o_ref[0, g] = out


def _paged_attention_call(q, k_pool, v_pool, tables, pos, k_scales=None,
                          v_scales=None, geometry=None, window=0, ring=0,
                          head_major=False):
    from ..autotune.kernel_geometry import (PagedAttentionGeometry,
                                            _largest_divisor)

    B, W, H, D = q.shape
    if head_major:
        N, KV, bs, _ = k_pool.shape
    else:
        N, bs, KV, _ = k_pool.shape
    rep = H // KV
    M = tables.shape[1]
    Wr = W * rep
    if not _interpret() and (D % 128 or bs % 8):
        # select.select_paged_attention keeps these shapes off the kernel
        raise ValueError(f"paged attention kernel needs head_dim % 128 == 0 "
                         f"and block_size % 8 == 0, got {D} / {bs}")
    quantized = k_scales is not None
    if bool(window) != bool(ring):
        raise ValueError(f"a sliding window is walked through a ring table: "
                         f"window and ring come together, got {window} / "
                         f"{ring}")
    if quantized and (window or ring or head_major):
        raise ValueError("the int8 pool has no sliding-window walk (its "
                         "scale tables are read a whole group at a time) "
                         "and no head-major layout")
    if geometry is None:
        geometry = _resolve("paged_attention",
                            "int8" if quantized else str(q.dtype), D)
    if not isinstance(geometry, PagedAttentionGeometry):
        raise ValueError(f"paged attention wants a PagedAttentionGeometry, "
                         f"got {type(geometry).__name__}")
    geometry.validate()
    R = Wr if geometry.q_rows == 0 else _largest_divisor(Wr, geometry.q_rows)
    NQ = Wr // R
    G, all_heads = group_plan(R, KV, bs, D, M, k_pool.dtype.itemsize,
                              geometry.kv_block_depth)
    early = quantized and geometry.dequant == "early"
    tables = tables.astype(jnp.int32)
    # GQA: group query heads with their shared kv head — (B, KV, W*rep, D).
    qt = q.reshape(B, W, KV, rep, D).transpose(0, 2, 1, 3, 4).reshape(
        B, KV, Wr, D)
    # grid: one program per batch row (x the optional q-row tile), both
    # parallel. The KV axis is NOT on the grid: each program loops over
    # its own row's live blocks, so the pools stay whole in HBM and the
    # program copies the blocks its table names.

    def q_map(b, *a):
        return (b, 0, a[0] if NQ > 1 else 0, 0)

    in_specs = [pl.BlockSpec((1, KV, R, D), q_map),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY)]
    args = [tables, pos.astype(jnp.int32), qt, k_pool, v_pool]
    if quantized:
        # per-row scale tables (B, M, KV) gathered through the block
        # table; width padded to whole groups
        Mp = -(-M // G) * G

        def row_scales(sc):
            return jnp.pad(sc.astype(jnp.float32)[tables],
                           ((0, 0), (0, Mp - M), (0, 0)))

        in_specs += [pl.BlockSpec((1, Mp, KV), lambda b, *a: (b, 0, 0))] * 2
        args += [row_scales(k_scales), row_scales(v_scales)]
    NG, rows = (1, KV * R) if all_heads else (KV, R)
    slots = (2, G, KV, bs, D) if head_major else (2, G * bs, KV, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NQ) if NQ > 1 else (B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, R, D), q_map),
        scratch_shapes=[
            pltpu.VMEM(slots, k_pool.dtype),            # K group slots
            pltpu.VMEM(slots, v_pool.dtype),            # V group slots
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((NG, rows, 128), jnp.float32),   # running max
            pltpu.VMEM((NG, rows, 128), jnp.float32),   # running sum
            pltpu.VMEM((NG, rows, D), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_attn_kernel, bs=bs, rep=rep, KV=KV, M=M, G=G, R=R,
                          quantized=quantized, early=early,
                          all_heads=all_heads, tiled=NQ > 1,
                          window=int(window), ring=int(ring),
                          head_major=bool(head_major)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Wr, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * (2 if NQ > 1 else 1)),
        interpret=_interpret(),
    )(*args)
    return out.reshape(B, KV, W, rep, D).transpose(0, 2, 1, 3, 4).reshape(
        B, W, H, D)


def paged_attention(q, k_pool, v_pool, block_tables, pos, geometry=None,
                    window=0, ring=0, head_major=False):
    """Fused paged decode/verify attention over an fp block pool.

    q: (B, W, H, D) — W=1 decode, W=tick_window verify, W=chunk prefill.
    pos: (B,) int — absolute position of each row's FIRST query token.
    geometry: trace-time :class:`PagedAttentionGeometry` (None = the
    process-wide winner cache, falling back to the default schedule).
    window > 0 and ring > 0, together: each query sees its last ``window``
    positions only, the walk starts at the block that holds the oldest of
    them, and the table is a ring of ``ring`` blocks (``block_tables`` (B,
    ring); logical block j lives in entry j % ring).
    head_major: the pools are ``(N, KV, bs, D)`` — a block holds each kv
    head's tokens as whole ``(bs, D)`` tiles, so a number of kv heads that
    is not a sublane multiple (10 packed pairs) costs no padding; the
    per-head body then reads a head's tiles with no stride.
    """
    return _paged_attention_call(q, k_pool, v_pool, block_tables, pos,
                                 geometry=geometry, window=window, ring=ring,
                                 head_major=head_major)


def paged_attention_q(q, kq_pool, k_scales, vq_pool, v_scales, block_tables,
                      pos, geometry=None):
    """Int8 twin: streams the code pool and dequantizes on the VMEM tile."""
    return _paged_attention_call(q, kq_pool, vq_pool, block_tables, pos,
                                 k_scales=k_scales, v_scales=v_scales,
                                 geometry=geometry)


# ----------------------------------------------------------------- LoRA BGMV
def _lora_kernel(s_ref, x_ref, w_ref, a_ref, b_ref, o_ref, *,
                 delta_first=False):
    x = x_ref[0]                               # (S, in)

    def base():
        return jax.lax.dot_general(x, w_ref[...], (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def delta():
        xa = jax.lax.dot_general(x.astype(jnp.float32), a_ref[0],
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        return jax.lax.dot_general(xa, b_ref[0], (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    # accumulation layout: which chain issues first — the final combine
    # is the same expression either way (bit-exact)
    if delta_first:
        d = delta()
        y = base()
    else:
        y = base()
        d = delta()
    o_ref[0] = (y + d * s_ref[pl.program_id(1)]).astype(o_ref.dtype)


def fused_lora_matmul(x, w, a, b, s, geometry=None):
    """Base projection + per-row LoRA delta in one program per (output
    tile, batch row): ``x @ w + ((x32 @ a[i]) @ b[i]) * s[i]``. The factors
    are the per-slot gathers from AdapterPool.gather_rows — a (B, in, R),
    b (B, R, out), s (B,); null adapters arrive as zero factors with s=0,
    making the delta exactly zero (bit-identical to the plain matmul).

    The base weight streams as ``(in, tile)`` column tiles
    (``select.lora_block_out`` sizes the tile from the VMEM budget; the
    output-tile axis is outermost so a tile stays resident across the
    batch rows) and the per-row scale is a scalar-prefetch SMEM operand.

    ``geometry`` (:class:`LoRAGeometry`): rank padding (zero columns/rows
    contribute exact zeros — bit-exact, MXU-aligned contraction) and the
    matmul issue order."""
    from ..autotune.kernel_geometry import LoRAGeometry
    from .select import lora_block_out

    B, S, IN = x.shape
    OUT = w.shape[1]
    R = a.shape[2]
    if geometry is None:
        geometry = _resolve("fused_lora", str(x.dtype), R)
    if not isinstance(geometry, LoRAGeometry):
        raise ValueError(f"fused LoRA wants a LoRAGeometry, got "
                         f"{type(geometry).__name__}")
    geometry.validate()
    rp = geometry.padded_rank(R)
    if rp != R:
        a = jnp.pad(a, ((0, 0), (0, 0), (0, rp - R)))
        b = jnp.pad(b, ((0, 0), (0, rp - R), (0, 0)))
        R = rp
    if _interpret():
        bn = OUT                     # the interpreter has no VMEM to fit
    else:
        bn = lora_block_out(S, IN, OUT, R, x.dtype, w.dtype)
        if not bn:
            # select.select_lora_matmul keeps these shapes off the kernel
            raise ValueError(f"fused LoRA: no output tile of a ({IN}, {OUT}) "
                             f"projection fits VMEM at S={S}, rank={R}")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(OUT // bn, B),
        in_specs=[
            pl.BlockSpec((1, S, IN), lambda j, i, s_: (i, 0, 0)),
            pl.BlockSpec((IN, bn), lambda j, i, s_: (0, j)),
            pl.BlockSpec((1, IN, R), lambda j, i, s_: (i, 0, 0)),
            pl.BlockSpec((1, R, bn), lambda j, i, s_: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, S, bn), lambda j, i, s_: (i, 0, j)),
    )
    return pl.pallas_call(
        functools.partial(_lora_kernel,
                          delta_first=geometry.accum == "delta_first"),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, S, OUT), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret(),
    )(s.astype(jnp.float32), x, w, a, b)
