"""Flash attention (Pallas TPU kernels).

Replaces the reference's CUDA FMHA stack (ref
paddle/fluid/operators/fused/fused_attention_op.cu, fmha_ref.h,
fused_softmax_mask kernels) with blockwise online-softmax kernels that never
materialise the S×S score matrix in HBM.

Kernel structure: 3-axis grids with the KV (resp. Q) block dimension as the
innermost "arbitrary" axis and fp32 VMEM scratch accumulators — KV streams
through VMEM block-by-block (Mosaic double-buffers the grid axis), so
sequence length is bounded by HBM, not by a resident full-K block. Forward
also emits per-row logsumexp; backward is the standard flash pair (dQ kernel
streaming KV; dK/dV kernel streaming Q/dO) using the saved LSE and
delta = rowsum(dO·O) precomputed by XLA. Causal variants skip fully-masked
blocks via pl.when (~2x at long S) and handle Sq != Sk with bottom-right
alignment. GQA: q heads route to shared kv heads through the BlockSpec index
map — no HBM repeat of K/V.

``select.select_flash_attention`` picks the kernels on a TPU and the jnp
composition elsewhere (CPU tests); a selected kernel that fails to compile
raises. Set PT_FLASH_INTERPRET=1 to exercise the Pallas kernels in
interpreter mode on CPU.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _use_pallas() -> bool:
    from .select import use_pallas

    return use_pallas()


def _interpret() -> bool:
    from .select import pallas_interpret

    return pallas_interpret()


def _vma_of(*arrays):
    """Union of varying-mesh-axes of traced inputs (shard_map check_vma):
    pallas out_shapes must declare how outputs vary across mesh axes."""
    vma = frozenset()
    for a in arrays:
        vma = vma | jax.typeof(a).vma
    return vma


def _sds(shape, dtype, vma):
    # always explicit: inside a check_vma shard_map an unset vma is an
    # error even when the operands vary over no axis (replicated island)
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _ref_bhsd(q, k, v, causal: bool, scale: float):
    """Reference composition, (B, H, S, D) layout, fp32 softmax. GQA: k/v may
    have Hkv | H heads."""
    if k.shape[1] != q.shape[1]:
        rep = q.shape[1] // k.shape[1]
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    logits = jnp.einsum("bhsd,bhtd->bhst", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhst,bhtd->bhsd", probs, v)


def _causal_mask(s, q_blk, kk, block_q, block_k, offs):
    q_pos = offs + q_blk * block_q + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)
    k_pos = kk * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _lanes(x):
    """Broadcast a (rows,) vector across the 128-lane scratch dim."""
    return jnp.broadcast_to(x[:, None], (x.shape[0], 128))


def _pick_block(n: int, want: int) -> int:
    """Largest of (want, 256, 128, n) that divides n — big blocks keep the
    MXU busy (512x512 measured ~2.3x over 128x128 at S=2048 on v5e), but the
    grid needs exact tiling."""
    for b in (want, 256, 128):
        if n % b == 0:
            return min(b, n)
    return n


def _mxu(x):
    """MXU operand dtype: keep bf16/f32 native; fold f64 (x64 test mode) to
    f32 so fp32 accumulators and carries type-match."""
    return x.astype(jnp.float32) if x.dtype == jnp.float64 else x


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                scale, causal, block_q, block_k, nk, seq_q, seq_k):
    """One (batch·head, q-block, k-block) program; k innermost with VMEM
    scratch (m, l, acc) carrying the online softmax across k steps."""
    from jax.experimental import pallas as pl
    scale = jnp.float32(scale)  # np.float64 scale must not promote f32 math

    q_blk = pl.program_id(1)
    kk = pl.program_id(2)
    offs = seq_k - seq_q

    @pl.when(kk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    if causal:
        needed = offs + (q_blk + 1) * block_q - 1 >= kk * block_k
    else:
        needed = True

    @pl.when(needed)
    def _compute():
        # keep matmul operands in the input dtype (bf16 hits the MXU at full
        # rate; an fp32 cast here runs ~7x slower) — fp32 only for softmax
        q = _mxu(q_ref[0])
        k = _mxu(k_ref[0])
        v = _mxu(v_ref[0])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, q_blk, kk, block_q, block_k, offs)
        m_prev = m_ref[:, 0]
        l_prev = l_ref[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = _lanes(l_prev * alpha + jnp.sum(p, axis=-1))
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = _lanes(m_new)

    @pl.when(kk == nk - 1)
    def _finish():
        m = m_ref[:, 0]
        l_safe = jnp.maximum(l_ref[:, 0], 1e-30)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = m + jnp.log(l_safe)


def _flash_fwd_bhsd_stream(q, k, v, causal: bool, scale: float,
                           block_q: int = 512, block_k: int = 512):
    """GQA-native: k/v may have fewer heads (Hkv | Hq); the kv BlockSpec
    index map routes each q head to its shared kv head — zero HBM copies
    (the reference materializes repeated KV; ref fmha_ref.h)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    rep = H // Hkv
    Sk = k.shape[2]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    nk = Sk // bk
    q_r = q.reshape(B * H, Sq, D)
    k_r = k.reshape(B * Hkv, Sk, D)
    v_r = v.reshape(B * Hkv, Sk, D)

    def kv_head(b):
        return (b // H) * Hkv + (b % H) // rep

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal, block_q=bq,
                          block_k=bk, nk=nk, seq_q=Sq, seq_k=Sk),
        grid=(B * H, Sq // bq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, kk: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, kk: (kv_head(b), kk, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, kk: (kv_head(b), kk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, kk: (b, i, 0)),
            # (BH, 1, Sq) with a singleton sublane dim satisfies the TPU
            # (8, 128) tiling rule for 1D-per-row outputs
            pl.BlockSpec((1, 1, bq), lambda b, i, kk: (b, 0, i)),
        ],
        out_shape=[
            _sds((B * H, Sq, D), q.dtype, _vma_of(q, k, v)),
            _sds((B * H, 1, Sq), jnp.float32, _vma_of(q, k, v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),  # m (lane-replicated)
            pltpu.VMEM((bq, 128), jnp.float32),  # l (lane-replicated)
            pltpu.VMEM((bq, D), jnp.float32),    # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q_r, k_r, v_r)
    return out.reshape(B, H, Sq, D), lse.reshape(B, H, Sq)


# --------------------------------------------------------------------------- #
# backward
# --------------------------------------------------------------------------- #


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_acc_ref, *, scale, causal, block_q, block_k, nk,
                   seq_q, seq_k):
    """dQ for one (batch·head, q-block): k blocks stream on the innermost
    grid axis. dS = P ∘ (dO·Vᵀ − delta); dQ = scale · dS·K."""
    from jax.experimental import pallas as pl
    scale = jnp.float32(scale)  # np.float64 scale must not promote f32 math

    q_blk = pl.program_id(1)
    kk = pl.program_id(2)
    offs = seq_k - seq_q

    @pl.when(kk == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    if causal:
        needed = offs + (q_blk + 1) * block_q - 1 >= kk * block_k
    else:
        needed = True

    @pl.when(needed)
    def _compute():
        q = _mxu(q_ref[0])
        do = _mxu(do_ref[0])
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        k = _mxu(k_ref[0])
        v = _mxu(v_ref[0])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, q_blk, kk, block_q, block_k, offs)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None])).astype(k.dtype)
        dq_acc_ref[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(kk == nk - 1)
    def _finish():
        dq_ref[0] = (dq_acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, scale, causal,
                    block_q, block_k, nq, seq_q, seq_k, rep):
    """dK/dV for one (batch·kv-head, k-block): the grid's two inner axes walk
    the ``rep`` q heads sharing this kv head, then stream q/dO blocks.
    dV = Pᵀ·dO; dK = scale · dSᵀ·Q (scale applied per-block on the dk dot).
    GQA gradients accumulate in VMEM scratch across the whole (rep, qi)
    plane — no redundant per-q-head kernel runs, no HBM rep-reduction."""
    from jax.experimental import pallas as pl
    scale = jnp.float32(scale)  # np.float64 scale must not promote f32 math

    k_blk = pl.program_id(1)
    r = pl.program_id(2)
    qi = pl.program_id(3)
    offs = seq_k - seq_q

    @pl.when(jnp.logical_and(r == 0, qi == 0))
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    if causal:
        needed = offs + (qi + 1) * block_q - 1 >= k_blk * block_k
    else:
        needed = True

    @pl.when(needed)
    def _compute():
        k = _mxu(k_ref[0])
        v = _mxu(v_ref[0])
        q = _mxu(q_ref[0, 0])
        do = _mxu(do_ref[0, 0])
        lse = lse_ref[0, 0, 0]
        delta = delta_ref[0, 0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, k_blk, block_q, block_k, offs)
        p = jnp.exp(s - lse[:, None])
        dv_acc_ref[...] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None])).astype(q.dtype)
        dk_acc_ref[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

    @pl.when(jnp.logical_and(r == rep - 1, qi == nq - 1))
    def _finish():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


def _flash_bwd_bhsd_stream(q, k, v, do, lse, delta, causal: bool, scale: float,
                           block_q: int = 512, block_k: int = 512):
    """Pallas flash backward. GQA-native: dq routes kv blocks per q head (no
    HBM repeat of K/V); dk/dv accumulate over the rep q heads inside the
    kernel grid (see _bwd_dkv_kernel) — no [B,H,Sk,D] intermediate."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    rep = H // Hkv
    Sk = k.shape[2]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    nq = Sq // bq
    nk = Sk // bk
    q_r = q.reshape(B * H, Sq, D)
    k_r = k.reshape(B * Hkv, Sk, D)
    v_r = v.reshape(B * Hkv, Sk, D)
    do_r = do.reshape(B * H, Sq, D)
    lse_r = lse.reshape(B * H, 1, Sq)
    delta_r = delta.reshape(B * H, 1, Sq)
    vma = _vma_of(q, k, v, do, lse, delta)

    def kv_head(b):
        return (b // H) * Hkv + (b % H) // rep

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nk=nk, seq_q=Sq, seq_k=Sk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, kk: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, kk: (kv_head(b), kk, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, kk: (kv_head(b), kk, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, kk: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, kk: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i, kk: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, kk: (b, i, 0)),
        out_shape=_sds((B * H, Sq, D), q.dtype, vma),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )(q_r, k_r, v_r, do_r, lse_r, delta_r)

    q_g = q.reshape(B * Hkv, rep, Sq, D)
    do_g = do.reshape(B * Hkv, rep, Sq, D)
    lse_g = lse.reshape(B * Hkv, rep, 1, Sq)
    delta_g = delta.reshape(B * Hkv, rep, 1, Sq)
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, nq=nq, seq_q=Sq, seq_k=Sk,
                          rep=rep),
        grid=(B * Hkv, nk, rep, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, kb, r, qi: (b, r, qi, 0)),
            pl.BlockSpec((1, bk, D), lambda b, kb, r, qi: (b, kb, 0)),
            pl.BlockSpec((1, bk, D), lambda b, kb, r, qi: (b, kb, 0)),
            pl.BlockSpec((1, 1, bq, D), lambda b, kb, r, qi: (b, r, qi, 0)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, kb, r, qi: (b, r, 0, qi)),
            pl.BlockSpec((1, 1, 1, bq), lambda b, kb, r, qi: (b, r, 0, qi)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, kb, r, qi: (b, kb, 0)),
            pl.BlockSpec((1, bk, D), lambda b, kb, r, qi: (b, kb, 0)),
        ],
        out_shape=[
            _sds((B * Hkv, Sk, D), k.dtype, vma),
            _sds((B * Hkv, Sk, D), v.dtype, vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=_interpret(),
    )(q_g, k_r, v_r, do_g, lse_g, delta_g)

    dq = dq.reshape(B, H, Sq, D)
    dk = dk.reshape(B, Hkv, Sk, D)
    dv = dv.reshape(B, Hkv, Sk, D)
    return dq, dk, dv


# --------------------------------------------------------------------------- #
# full-K fori-loop variants — faster when K/V fit VMEM (better block reuse
# than the streaming grid); dispatcher picks by Sk (see _flash_dispatch)
# --------------------------------------------------------------------------- #

def _fwd_kernel_loop(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_k, seq_k, seq_q):
    """One (batch·head, q-block) program: stream KV blocks, online softmax.
    Also writes the per-row logsumexp (flash backward needs it)."""
    from jax.experimental import pallas as pl
    scale = jnp.float32(scale)  # np.float64 scale must not promote f32 math

    q = _mxu(q_ref[0])  # (block_q, d) — native dtype: bf16 operands hit the MXU at
    block_q = q.shape[0]  # full rate (fp32-cast dots run ~7x slower)
    d = q.shape[-1]
    q_blk = pl.program_id(1)

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)

    num_k_blocks = seq_k // block_k

    def body(i, carry):
        m, l, acc = carry
        k = _mxu(k_ref[0, pl.dslice(i * block_k, block_k), :])
        v = _mxu(v_ref[0, pl.dslice(i * block_k, block_k), :])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            # bottom-right alignment for Sq != Sk (ref tril k=Sk-Sq)
            s = _causal_mask(s, q_blk, i, block_q, block_k, seq_k - seq_q)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        # only stream blocks up to (and including) the diagonal
        last = (seq_k - seq_q) + (q_blk + 1) * block_q
        n_needed = (last + block_k - 1) // block_k
        upper = jnp.minimum(n_needed, num_k_blocks)
    else:
        upper = num_k_blocks
    m, l, acc = jax.lax.fori_loop(0, upper, body, (m0, l0, acc0))
    l_safe = jnp.maximum(l, 1e-30)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, 0] = m + jnp.log(l_safe)


def _flash_fwd_bhsd_loop(q, k, v, causal: bool, scale: float, block_q: int = 512,
                    block_k: int = 512):
    """GQA-native: k/v may have fewer heads (Hkv | Hq); the kv BlockSpec
    index map routes each q head to its shared kv head — zero HBM copies
    (the reference materializes repeated KV; ref fmha_ref.h)."""
    from jax.experimental import pallas as pl

    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    rep = H // Hkv
    Sk = k.shape[2]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    q_r = q.reshape(B * H, Sq, D)
    k_r = k.reshape(B * Hkv, Sk, D)
    v_r = v.reshape(B * Hkv, Sk, D)

    def kv_index(b, i):
        return (b // H) * Hkv + (b % H) // rep, 0, 0

    grid = (B * H, Sq // bq)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel_loop, scale=scale, causal=causal, block_k=bk,
                          seq_k=Sk, seq_q=Sq),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, D), kv_index),
            pl.BlockSpec((1, Sk, D), kv_index),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            # (BH, 1, Sq) with a singleton sublane dim satisfies the TPU
            # (8, 128) tiling rule for 1D-per-row outputs
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
        ],
        out_shape=[
            _sds((B * H, Sq, D), q.dtype, _vma_of(q, k, v)),
            _sds((B * H, 1, Sq), jnp.float32, _vma_of(q, k, v)),
        ],
        interpret=_interpret(),
    )(q_r, k_r, v_r)
    return out.reshape(B, H, Sq, D), lse.reshape(B, H, Sq)


def _bwd_dq_kernel_loop(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
                   scale, causal, block_k, seq_k, seq_q):
    """dQ for one (batch·head, q-block): stream KV, use saved LSE.
    dS = P ∘ (dO·Vᵀ − delta); dQ = scale · dS·K  (flash-attention backward)."""
    from jax.experimental import pallas as pl
    scale = jnp.float32(scale)  # np.float64 scale must not promote f32 math

    q = _mxu(q_ref[0])
    do = _mxu(do_ref[0])
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]
    block_q, d = q.shape
    q_blk = pl.program_id(1)
    num_k_blocks = seq_k // block_k

    def body(i, dq_acc):
        k = _mxu(k_ref[0, pl.dslice(i * block_k, block_k), :])
        v = _mxu(v_ref[0, pl.dslice(i * block_k, block_k), :])
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, q_blk, i, block_q, block_k, seq_k - seq_q)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None])).astype(k.dtype)
        return dq_acc + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    if causal:
        last = (seq_k - seq_q) + (q_blk + 1) * block_q
        upper = jnp.minimum((last + block_k - 1) // block_k, num_k_blocks)
    else:
        upper = num_k_blocks
    dq = jax.lax.fori_loop(0, upper, body,
                           jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel_loop(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, seq_q, seq_k):
    """dK/dV for one (batch·head, k-block): stream Q/dO blocks.
    dV = Pᵀ·dO; dK = scale · dSᵀ·Q.

    GQA note: this full-K form runs per *query* head and reduces dk/dv over
    the rep group afterwards. Folding the rep axis into the grid (as the
    stream form does) was measured 2026-07: rep-innermost refetches the full
    Sq·D q/dO slab Sk/bk times — a net HBM regression; rep-outermost breaks
    the consecutive-revisit rule for the output accumulator. The redundant
    [B,H,Sk,D] intermediate is ~16 MB at the S≤8192 sizes this form serves."""
    from jax.experimental import pallas as pl
    scale = jnp.float32(scale)  # np.float64 scale must not promote f32 math

    k = _mxu(k_ref[0])
    v = _mxu(v_ref[0])
    block_k, d = k.shape
    k_blk = pl.program_id(1)
    num_q_blocks = seq_q // block_q

    def body(i, carry):
        dk_acc, dv_acc = carry
        q = _mxu(q_ref[0, pl.dslice(i * block_q, block_q), :])
        do = _mxu(do_ref[0, pl.dslice(i * block_q, block_q), :])
        lse = lse_ref[0, 0, pl.dslice(i * block_q, block_q)]
        delta = delta_ref[0, 0, pl.dslice(i * block_q, block_q)]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, i, k_blk, block_q, block_k, seq_k - seq_q)
        p = jnp.exp(s - lse[:, None])
        dv_new = dv_acc + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta[:, None])).astype(q.dtype)
        dk_new = dk_acc + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        return dk_new, dv_new

    if causal:
        # first q block that can see this k block (bottom-right aligned)
        lower = jnp.maximum(k_blk * block_k - (seq_k - seq_q), 0) // block_q
    else:
        lower = 0
    dk, dv = jax.lax.fori_loop(lower, num_q_blocks, body,
                               (jnp.zeros((block_k, d), jnp.float32),
                                jnp.zeros((block_k, d), jnp.float32)))
    dk_ref[0] = dk.astype(dk_ref.dtype)  # scale applied per-block in body
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _flash_bwd_bhsd_loop(q, k, v, do, lse, delta, causal: bool, scale: float,
                    block_q: int = 512, block_k: int = 512):
    """Pallas flash backward. GQA: dk/dv are computed per q-head with the
    same kv BlockSpec routing as forward (no HBM repeat of K/V), then summed
    over the rep group."""
    from jax.experimental import pallas as pl

    B, H, Sq, D = q.shape
    Hkv = k.shape[1]
    rep = H // Hkv
    Sk = k.shape[2]
    bq = _pick_block(Sq, block_q)
    bk = _pick_block(Sk, block_k)
    q_r = q.reshape(B * H, Sq, D)
    k_r = k.reshape(B * Hkv, Sk, D)
    v_r = v.reshape(B * Hkv, Sk, D)
    do_r = do.reshape(B * H, Sq, D)
    lse_r = lse.reshape(B * H, 1, Sq)
    delta_r = delta.reshape(B * H, 1, Sq)

    def kv_index(b, i):
        return (b // H) * Hkv + (b % H) // rep, 0, 0

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel_loop, scale=scale, causal=causal,
                          block_k=bk, seq_k=Sk, seq_q=Sq),
        grid=(B * H, Sq // bq),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, D), kv_index),
            pl.BlockSpec((1, Sk, D), kv_index),
            pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i: (b, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
        out_shape=_sds((B * H, Sq, D), q.dtype,
                       _vma_of(q, k, v, do, lse, delta)),
        interpret=_interpret(),
    )(q_r, k_r, v_r, do_r, lse_r, delta_r)

    dk_h, dv_h = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel_loop, scale=scale, causal=causal,
                          block_q=bq, seq_q=Sq, seq_k=Sk),
        grid=(B * H, Sk // bk),
        in_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i: (kv_index(b, i)[0], i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i: (kv_index(b, i)[0], i, 0)),
            pl.BlockSpec((1, Sq, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, Sq), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, 1, Sq), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            _sds((B * H, Sk, D), k.dtype, _vma_of(q, k, v, do, lse, delta)),
            _sds((B * H, Sk, D), v.dtype, _vma_of(q, k, v, do, lse, delta)),
        ],
        interpret=_interpret(),
    )(q_r, k_r, v_r, do_r, lse_r, delta_r)

    dq = dq.reshape(B, H, Sq, D)
    dk = dk_h.reshape(B, Hkv, rep, Sk, D).sum(axis=2).astype(k.dtype)
    dv = dv_h.reshape(B, Hkv, rep, Sk, D).sum(axis=2).astype(v.dtype)
    return dq, dk, dv



# K/V longer than this stream block-by-block through the 3-axis grid; below
# it the full-K loop kernels win (K/V stay resident in VMEM across q blocks)
_FULL_K_MAX = 8192


#: per-sequence-length-regime (block_q, block_k) defaults — populated from
#: tools/bench_flash_sweep.py winners measured on a real v5e chip
#: (2026-07-31, dispatch-chain differencing).  Key = max seq len of the
#: regime (entries ascending).  Forward and backward want DIFFERENT blocks:
#: at S=2048 GQA the fwd kernel runs 1.2-1.9 ms at 512x1024 vs 2.05 ms at
#: 512x512, while fwd+bwd is fastest with the bwd kernel at 512x512
#: (4.57 ms vs 4.98 ms uniform 512x1024) — so the tables are split.
#: S=16384 streaming regime: 1024x1024 measured 7.47 ms fwd (147 TFLOP/s,
#: 75% of v5e peak) and 32.3 ms fwd+bwd — best for both directions.  The
#: 8192 boundary (largest shape still on the full-K LOOP kernels, see
#: _FULL_K_MAX) was swept separately: 512x512 wins both directions there
#: (5.02 ms fwd / 18.6 ms fwd+bwd at B=2; 1024x1024 fails on the loop
#: kernels' VMEM residency).
_BLOCK_REGIMES_FWD = {
    4096: (512, 1024),
    8192: (512, 512),
    16384: (1024, 1024),
}
#: MHA (KV == H, GPT family) wants smaller K blocks at short S than GQA:
#: measured 2026-07-31 on v5e, H16/KV16 S=2048 fwd — 512x512 at 2.05 ms
#: (pairwise median) vs 8% slower at the GQA winner 512x1024.  Long-S
#: entries inherit the GQA table (the streaming regime is
#: head-ratio-insensitive), so retunes there propagate automatically.
_BLOCK_REGIMES_FWD_MHA = {**_BLOCK_REGIMES_FWD, 4096: (512, 512)}
_BLOCK_REGIMES_BWD = {
    4096: (512, 512),
    8192: (512, 512),
    16384: (1024, 1024),
}


def _block_defaults(seq_len: int = 0, kind: str = "fwd", mha: bool = False):
    """Tuning knobs per shape regime (benchmarked via bench.py A/B and
    tools/bench_flash_sweep.py).  Override order: PT_FLASH_BLOCK_Q/K
    (global, both directions) > PT_FLASH_BLOCKS (forward ONLY) /
    PT_FLASH_BLOCKS_BWD (backward ONLY) regime maps
    ("4096:512x512,16384:1024x512") > the split _BLOCK_REGIMES_FWD/_BWD
    tables, with the forward table keyed on the KV/H ratio (MHA gets its
    own measured column — tables exist so users don't need env overrides).
    The fwd env var deliberately does NOT leak into the backward kernel:
    adopting a fwd-sweep winner must not undo the measured bwd default
    (bwd prefers smaller K blocks than fwd on every swept shape)."""
    import os

    if os.environ.get("PT_FLASH_BLOCK_Q") or os.environ.get("PT_FLASH_BLOCK_K"):
        return (int(os.environ.get("PT_FLASH_BLOCK_Q", 512)),
                int(os.environ.get("PT_FLASH_BLOCK_K", 512)))
    regimes = dict(_BLOCK_REGIMES_BWD if kind == "bwd" else
                   (_BLOCK_REGIMES_FWD_MHA if mha else _BLOCK_REGIMES_FWD))
    env_map = os.environ.get(
        "PT_FLASH_BLOCKS_BWD" if kind == "bwd" else "PT_FLASH_BLOCKS")
    if env_map:
        try:
            for part in env_map.split(","):
                s, blocks = part.split(":")
                bq, bk = blocks.lower().split("x")
                regimes[int(s)] = (int(bq), int(bk))
        except ValueError:
            pass  # malformed override: keep the table
    for cap in sorted(regimes):
        if seq_len <= cap:
            return regimes[cap]
    return regimes[max(regimes)]


def _geometry_blocks(q):
    """Profile-resolved FlashAttentionGeometry override, consulted at
    trace time when the caller left block_q/block_k unset. Precedence:
    explicit args > PT_FLASH_BLOCK_Q/K and PT_FLASH_BLOCKS env overrides
    > the winner cache > the measured regime tables. Forward only — a
    fwd-swept winner must not undo the measured bwd defaults (same rule
    the env vars follow). Zero fields mean "no opinion" and fall through
    to the tables; ``_pick_block`` still clamps onto the shape."""
    import os

    if (os.environ.get("PT_FLASH_BLOCK_Q")
            or os.environ.get("PT_FLASH_BLOCK_K")
            or os.environ.get("PT_FLASH_BLOCKS")):
        return None, None
    from ..autotune.kernel_geometry import (active_geometry_cache,
                                            resolve_geometry)

    if active_geometry_cache() is None:
        return None, None
    geom, src = resolve_geometry("flash_attention", str(q.dtype), q.shape[3])
    if src == "default":
        return None, None
    return geom.block_q or None, geom.block_kv or None


def _flash_fwd_bhsd(q, k, v, causal, scale, block_q=None, block_k=None):
    if block_q is None and block_k is None:
        block_q, block_k = _geometry_blocks(q)
    dq, dk = _block_defaults(k.shape[2], mha=k.shape[1] == q.shape[1])
    block_q, block_k = block_q or dq, block_k or dk
    if k.shape[2] <= _FULL_K_MAX:
        return _flash_fwd_bhsd_loop(q, k, v, causal, scale, block_q, block_k)
    return _flash_fwd_bhsd_stream(q, k, v, causal, scale, block_q, block_k)


def _flash_bwd_bhsd(q, k, v, do, lse, delta, causal, scale,
                    block_q=None, block_k=None):
    dq, dk = _block_defaults(k.shape[2], kind="bwd")
    block_q, block_k = block_q or dq, block_k or dk
    if k.shape[2] <= _FULL_K_MAX:
        return _flash_bwd_bhsd_loop(q, k, v, do, lse, delta, causal, scale,
                                    block_q, block_k)
    return _flash_bwd_bhsd_stream(q, k, v, do, lse, delta, causal, scale,
                                  block_q, block_k)


# --------------------------------------------------------------------------- #
# public custom-vjp entry points
# --------------------------------------------------------------------------- #


def _select(q, k) -> str:
    from .select import record, select_flash_attention

    return record("flash_attention", select_flash_attention(q.shape, k.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=False, scale=None):
    """(B, H, S, D) flash attention. scale defaults to 1/sqrt(D)."""
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if _select(q, k) != "xla":
        return _flash_fwd_bhsd(q, k, v, causal, s)[0]
    return _ref_bhsd(q, k, v, causal, s)


def _fa_fwd(q, k, v, causal, scale):
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if _select(q, k) != "xla":
        out, lse = _flash_fwd_bhsd(q, k, v, causal, s)
        return out, (q, k, v, out, lse)
    return _ref_bhsd(q, k, v, causal, s), (q, k, v, None, None)


def _fa_bwd(causal, scale, res, g):
    q, k, v, out, lse = res
    s = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if lse is not None:
        delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                        axis=-1)  # rowsum(dO·O); XLA fuses this reduction
        return _flash_bwd_bhsd(q, k, v, g, lse, delta, causal, s)
    # the forward took the reference composition: differentiate that
    _, vjp_fn = jax.vjp(lambda q_, k_, v_: _ref_bhsd(q_, k_, v_, causal, s), q, k, v)
    return vjp_fn(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_bshd(q, k, v, causal=False, scale=None):
    """Paddle head layout (B, S, H, D) wrapper. GQA-aware: k/v may carry
    fewer heads (Hkv | Hq) — the kernel routes q heads to shared kv heads via
    its index map, no repeat."""
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    out = flash_attention(qh, kh, vh, causal, scale)
    return jnp.swapaxes(out, 1, 2)
