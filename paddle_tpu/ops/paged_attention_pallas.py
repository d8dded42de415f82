"""Pallas TPU kernels for the paged serving hot path.

One flash-style online-softmax kernel serves decode (W=1), speculative
verify (W=tick_window), and chunked prefill (B=1, W=chunk): the grid is
(batch, kv_block) and the K/V ``BlockSpec`` index_map reads the block
table through ``PrefetchScalarGridSpec`` scalar-prefetch — ``tbl[b, m]``
picks the pool block to stream into VMEM, so the dense ``gather_block_kv``
copy of the context never materializes in HBM. One program streams WHOLE
``(bs, KV, D)`` blocks and walks the kv heads inside (Mosaic only takes
blocks whose last two dims are the pool's own ``(KV, D)``). Running
max/sum/accumulator live in VMEM scratch across the block axis;
``pl.when`` skips blocks past each row's causal frontier, which also
covers the all-zero scratch-block entries of short sequences. The int8
twin streams the code pool directly and applies the per-(block, kv-head)
scales on the VMEM tile — k-scale on the fp32 QK accumulator, v-scale
folded into the probabilities before PV — so a dequantized pool is never
built. ``fused_lora_matmul`` fuses the per-slot BGMV adapter delta
(gathered A/B/scale factors) into the base projection matmul, one program
per (output tile, batch row).

The kernel's SCHEDULE is parameterized by
:class:`~paddle_tpu.autotune.kernel_geometry.PagedAttentionGeometry`
(and the LoRA kernel's by :class:`~paddle_tpu.autotune.kernel_geometry
.LoRAGeometry`): KV streaming depth (blocks fetched per grid step),
q-row tiling (extra parallel axis over the W*rep GQA rows), and int8
cast placement. All geometry axes are
schedule-only — the per-block online-softmax update runs in the same
order on the same values, so every geometry is bit-exact against the
default (one block per step, full row group). ``geometry=``
is a trace-time parameter; when omitted, the process-wide winner cache
(``autotune.kernel_geometry.install_geometry_cache``) is consulted at
trace time, same contract as ``ops.set_kernel_mode``.

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


def _lanes(x):
    """Broadcast a (rows,) vector across the 128-lane minor dim so the
    running max/sum scratch keeps a TPU-native (rows, 128) layout."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], 128))


def _resolve(op: str, dtype: str, key: int):
    from ..autotune.kernel_geometry import resolve_geometry

    return resolve_geometry(op, dtype, key)[0]


# ------------------------------------------------------------------ attention
def _attn_kernel(tbl_ref, pos_ref, q_ref, *rest, bs, W, rep, KV, Mp, depth,
                 R, quantized, early, ib, iq, im):
    d = depth
    k_refs = rest[:d]
    v_refs = rest[d:2 * d]
    n = 2 * d
    if quantized:
        ks_refs = rest[n:n + d]
        vs_refs = rest[n + d:n + 2 * d]
        n += 2 * d
    else:
        ks_refs = vs_refs = None
    o_ref, m_ref, l_ref, acc_ref = rest[n:]
    b = pl.program_id(ib)
    m = pl.program_id(im)
    # first global q row of this program's tile (0 unless q_rows tiles
    # the W*rep group across its own grid axis)
    row0 = pl.program_id(iq) * R if iq is not None else 0

    @pl.when(m == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _step(j):
        blk = m * d + j
        # Skip blocks entirely past the last query row's causal frontier
        # — this also covers block-table tail entries that still point at
        # scratch block 0. The frontier test is per batch row (not per
        # q tile) so the skip schedule is geometry-independent.
        needed = blk * bs <= pos_ref[b] + (W - 1)
        if quantized and early:
            # "early" dequant placement: the int8->fp cast is exact, so
            # hoisting it out of the skip branch changes the schedule
            # (branchless stream) but never the math
            k_pre = [k_refs[j][0, :, g, :].astype(q_ref.dtype)
                     for g in range(KV)]
            v_pre = [v_refs[j][0, :, g, :].astype(q_ref.dtype)
                     for g in range(KV)]

        @pl.when(needed)
        def _compute():
            # the whole (bs, KV, D) block is resident; each kv head's
            # online-softmax state is independent, so walking the heads
            # here runs, per head, exactly the per-block update order of
            # a one-head-per-program grid
            for g in range(KV):
                q = q_ref[0, g]                       # (R, D)
                if quantized and early:
                    k, v = k_pre[g], v_pre[g]
                else:
                    k = k_refs[j][0, :, g, :]         # (bs, D)
                    v = v_refs[j][0, :, g, :]
                    if quantized:
                        k = k.astype(q.dtype)
                        v = v.astype(q.dtype)
                s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
                if quantized:
                    # reference order: scores * k_scale, then / sqrt(D)
                    s = s * ks_refs[j][0, :, g:g + 1]
                s = s / jnp.float32(math.sqrt(q.shape[-1]))
                rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
                cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                # row -> absolute query position
                qpos = pos_ref[b] + (row0 + rows) // rep
                s = jnp.where(blk * bs + cols <= qpos, s, NEG_INF)
                m_prev = m_ref[g, :, 0]
                l_prev = l_ref[g, :, 0]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
                p = jnp.exp(s - m_new[:, None])
                alpha = jnp.exp(m_prev - m_new)
                l_ref[g] = _lanes(l_prev * alpha + jnp.sum(p, axis=-1))
                if quantized:
                    p = p * vs_refs[j][0, :, g:g + 1]  # v scale into probs
                acc_ref[g] = acc_ref[g] * alpha[:, None] + \
                    jax.lax.dot_general(
                        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
                m_ref[g] = _lanes(m_new)

    for j in range(d):
        _step(j)

    @pl.when(m == Mp - 1)
    def _finish():
        for g in range(KV):
            l_safe = jnp.maximum(l_ref[g, :, 0], 1e-30)
            o_ref[0, g] = (acc_ref[g] / l_safe[:, None]).astype(o_ref.dtype)


def _paged_attention_call(q, k_pool, v_pool, tables, pos, k_scales=None,
                          v_scales=None, geometry=None):
    from ..autotune.kernel_geometry import (PagedAttentionGeometry,
                                            _largest_divisor)

    B, W, H, D = q.shape
    N, bs, KV, _ = k_pool.shape
    rep = H // KV
    M = tables.shape[1]
    Wr = W * rep
    if not _interpret() and (D % 128 or bs % 8):
        # select.select_paged_attention keeps these shapes off the kernel
        raise ValueError(f"paged attention kernel needs head_dim % 128 == 0 "
                         f"and block_size % 8 == 0, got {D} / {bs}")
    quantized = k_scales is not None
    if geometry is None:
        geometry = _resolve("paged_attention",
                            "int8" if quantized else str(q.dtype), D)
    if not isinstance(geometry, PagedAttentionGeometry):
        raise ValueError(f"paged attention wants a PagedAttentionGeometry, "
                         f"got {type(geometry).__name__}")
    geometry.validate()
    # geometry values quantize onto this shape deterministically
    depth = _largest_divisor(M, geometry.kv_block_depth)
    R = Wr if geometry.q_rows == 0 else _largest_divisor(Wr, geometry.q_rows)
    NQ = Wr // R
    Mp = M // depth
    early = quantized and geometry.dequant == "early"
    # GQA: group query heads with their shared kv head — (B, KV, W*rep, D).
    qt = q.reshape(B, W, KV, rep, D).transpose(0, 2, 1, 3, 4).reshape(
        B, KV, Wr, D)
    # grid: batch (parallel), the optional q-row tile axis (parallel), then
    # the sequential kv-block axis. The kv heads are walked INSIDE the
    # program: Mosaic only takes K/V blocks whose last two dims are the
    # pool's own (KV, D), so one program streams whole (bs, KV, D) blocks.
    # ``geometry.grid_order`` ordered the former (batch, kv-head) grid axes
    # and is moot now; it is still validated so swept profiles load.
    axes = ["b"] + (["q"] if NQ > 1 else []) + ["m"]
    sizes = {"b": B, "q": NQ, "m": Mp}
    grid = tuple(sizes[a] for a in axes)
    ib, im = axes.index("b"), axes.index("m")
    iq = axes.index("q") if NQ > 1 else None

    def q_map(*a):
        ids = a[:-2]
        return (ids[ib], 0, ids[iq] if iq is not None else 0, 0)

    def kv_map(j):
        def f(*a):
            ids, tbl = a[:-2], a[-2]
            return (tbl[ids[ib], ids[im] * depth + j], 0, 0, 0)
        return f

    def sc_map(j):
        def f(*a):
            ids, tbl = a[:-2], a[-2]
            return (tbl[ids[ib], ids[im] * depth + j], 0, 0)
        return f

    in_specs = [pl.BlockSpec((1, KV, R, D), q_map)]
    in_specs += [pl.BlockSpec((1, bs, KV, D), kv_map(j))
                 for j in range(depth)]
    in_specs += [pl.BlockSpec((1, bs, KV, D), kv_map(j))
                 for j in range(depth)]
    args = [tables.astype(jnp.int32), pos.astype(jnp.int32), qt]
    args += [k_pool] * depth + [v_pool] * depth
    if quantized:
        # (N, KV) scales ride as (N, 1, KV) so a block's last two dims are
        # the array's own
        in_specs += [pl.BlockSpec((1, 1, KV), sc_map(j))
                     for j in range(depth)]
        in_specs += [pl.BlockSpec((1, 1, KV), sc_map(j))
                     for j in range(depth)]
        args += [k_scales.astype(jnp.float32).reshape(N, 1, KV)] * depth
        args += [v_scales.astype(jnp.float32).reshape(N, 1, KV)] * depth
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, KV, R, D), q_map),
        scratch_shapes=[
            pltpu.VMEM((KV, R, 128), jnp.float32),   # running max
            pltpu.VMEM((KV, R, 128), jnp.float32),   # running sum
            pltpu.VMEM((KV, R, D), jnp.float32),     # output accumulator
        ],
    )
    out = pl.pallas_call(
        functools.partial(_attn_kernel, bs=bs, W=W, rep=rep, KV=KV, Mp=Mp,
                          depth=depth, R=R, quantized=quantized,
                          early=early, ib=ib, iq=iq, im=im),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Wr, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=tuple(
                "arbitrary" if a == "m" else "parallel" for a in axes)),
        interpret=_interpret(),
    )(*args)
    return out.reshape(B, KV, W, rep, D).transpose(0, 2, 1, 3, 4).reshape(
        B, W, H, D)


def paged_attention(q, k_pool, v_pool, block_tables, pos, geometry=None):
    """Fused paged decode/verify attention over an fp block pool.

    q: (B, W, H, D) — W=1 decode, W=tick_window verify, W=chunk prefill.
    pos: (B,) int — absolute position of each row's FIRST query token.
    geometry: trace-time :class:`PagedAttentionGeometry` (None = the
    process-wide winner cache, falling back to the default schedule).
    """
    return _paged_attention_call(q, k_pool, v_pool, block_tables, pos,
                                 geometry=geometry)


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
