"""Paged KV-cache attention — block-table decode, chunked-prefill and
multi-token (speculative verify) window ops.

The serving KV cache stops being a dense ``(max_batch, max_len, KV, D)``
slab and becomes a POOL of fixed-size blocks ``(num_blocks, block_size,
KV, D)`` plus per-request block tables (int32 rows of block ids). HBM is
then proportional to *active* tokens, not to ``max_batch · max_len``
(vLLM's PagedAttention, adapted to the XLA/TPU constraints: static shapes
everywhere, tables are data not shapes).

Layout is chosen Pallas-ready, mirroring the flash kernels in
``flash_attention.py``:

- pools are BLOCK-MAJOR ``(N, bs, KV, D)`` so one block's K (or V) is a
  contiguous ``(bs, KV, D)`` tile — exactly the unit a Mosaic kernel
  streams through VMEM;
- block tables are small int32 operands — on TPU they become
  ``PrefetchScalarGridSpec`` scalar-prefetch args that name the blocks
  each program copies (the kernel walks ``table[i]`` instead of ``i``,
  which is the whole trick of paged attention);
- the decode gather and the chunk scatter below are the pure-jnp
  REFERENCE path: CPU tier-1 runs it bit-for-bit.

The real kernels live in ``paged_attention_pallas.py``: one flash-style
online-softmax kernel covering decode (W=1), speculative verify
(W=tick_window) and chunked prefill (B=1), fp and int8-fused-dequant. The
public attention functions below select them by the
``select.select_paged_attention`` rule (a TPU, ``PT_FLASH_INTERPRET=1``, or
``ops.set_kernel_mode("pallas")``; never under a GSPMD-partitioned trace)
and otherwise run the jnp reference via one parameterized
``_attention_core`` — a single seam instead of six twins.

All masks/softmax run in fp32 with the same ``-1e30`` fill as the dense
decode path (``models/llama.py LlamaAttention.decode``) so greedy outputs
stay token-exact between dense and paged servers.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def gather_block_kv(pool, block_tables, head_major=False):
    """Gather per-row K (or V) context from the block pool.

    pool: (N, bs, KV, D), or (N, KV, bs, D) ``head_major``; block_tables:
    int32 (B, M) (or (M,) for one row). Returns (B, M*bs, KV, D) — the
    dense-equivalent context window, where table entry 0 conventionally
    points at the scratch block and is masked out by the caller's position
    mask.
    """
    bt = block_tables if block_tables.ndim == 2 else block_tables[None]
    gathered = pool[bt]                       # (B, M, bs, KV, D)
    if head_major:
        gathered = gathered.swapaxes(2, 3)
    b, m, bs, kv, d = gathered.shape
    return gathered.reshape(b, m * bs, kv, d)


def write_window_kv(k_pool, v_pool, k, v, block_tables, pos,
                    head_major=False):
    """Scatter a WINDOW of new tokens' K/V per row through the block table.

    k/v: (B, W, KV, D); block_tables: (B, M); pos: int32 (B,) — row ``b``'s
    token ``j`` lands at position ``pos[b] + j``, i.e. at
    ``(table[b, (pos+j)//bs], (pos+j)%bs)``. W = 1 is the plain decode
    write; W = k+1 is the speculative verify window (positions past the
    accepted prefix hold rejected-token K/V that the NEXT window
    overwrites before any query can attend it). Rows the server parked on
    the scratch block (idle/prefilling slots) harmlessly overwrite
    scratch.
    """
    bs = k_pool.shape[2 if head_major else 1]
    W = k.shape[1]
    pj = pos[:, None] + jnp.arange(W)[None, :]          # (B, W)
    bid = jnp.take_along_axis(block_tables, pj // bs, axis=1)
    return _set_tokens(k_pool, v_pool, k, v, bid, pj % bs, head_major)


def _set_tokens(k_pool, v_pool, k, v, bid, off, head_major):
    """pool[block ``bid``, token ``off``] = k, v (leading dims alike; k, v
    (..., KV, D)). A head-major pool is written through its ``(N*KV*bs,
    D)`` row view (a bitcast: whole tiles merge), one row per kv head: a
    scatter over dimensions 0 and 2 of the 4-D pool makes XLA:TPU copy the
    WHOLE pool into a token-major layout and back around it (seen in the
    compiled decode program of Phi-4-mini-flash: 4 x 1.3 GB a tick)."""
    if not head_major:
        return (k_pool.at[bid, off].set(k.astype(k_pool.dtype)),
                v_pool.at[bid, off].set(v.astype(v_pool.dtype)))
    N, KV, bs, D = k_pool.shape
    rows = ((bid[..., None] * KV + jnp.arange(KV)) * bs
            + off[..., None]).reshape(-1)

    def put(pool, x):
        flat = pool.reshape(N * KV * bs, D)
        return flat.at[rows].set(x.reshape(-1, D).astype(pool.dtype)
                                 ).reshape(pool.shape)

    return put(k_pool, k), put(v_pool, v)


def write_decode_kv(k_pool, v_pool, k, v, block_tables, pos,
                    head_major=False):
    """Scatter ONE new token's K/V per row — :func:`write_window_kv` at
    W = 1. k/v: (B, KV, D)."""
    return write_window_kv(k_pool, v_pool, k[:, None], v[:, None],
                           block_tables, pos, head_major)


def write_chunk_kv(k_pool, v_pool, k, v, block_table, start,
                   head_major=False):
    """Scatter a prefill CHUNK's K/V into consecutive table entries.

    k/v: (C, KV, D) with C a multiple of ``bs``; block_table: (M,);
    start: traced int32, block-aligned chunk origin. The chunk occupies
    table entries [start//bs, start//bs + C//bs) — a dynamic_slice of the
    table, then one blocked scatter (the Pallas version would walk the
    same slice as scalar-prefetch grid indices).
    """
    bs = k_pool.shape[2 if head_major else 1]
    nb = k.shape[0] // bs
    blocks = jax.lax.dynamic_slice_in_dim(block_table, start // bs, nb, 0)

    def tiles(x, pool):
        x = x.reshape(nb, bs, *x.shape[1:])
        return (x.swapaxes(1, 2) if head_major else x).astype(pool.dtype)

    return (k_pool.at[blocks].set(tiles(k, k_pool)),
            v_pool.at[blocks].set(tiles(v, v_pool)))


def _attention_core(q, ck, cv, qpos, ksl=None, vsl=None):
    """The ONE parameterized jnp attention skeleton behind all six public
    attention entry points — grouped GQA einsum, fp32 scores, positional
    causal mask, fp32 softmax. ``qpos`` is the (B, S) absolute position of
    every query row/token; fused int8 dequant engages when the per-token
    ``ksl``/``vsl`` scale views (B, L, KV) are given — k's scale multiplies
    the fp32 QK accumulator ((q·k_q)·s == q·(k_q·s), per-kv-head scales
    commute with the D-contraction), v's scale folds into p before the V
    accumulation (p·(v_q·s) == (p·s)·v_q), so a dequantized pool is never
    materialized. Bit-identical to the pre-dedupe twins."""
    B, S, H, D = q.shape
    KV = ck.shape[2]
    rep = H // KV
    L = ck.shape[1]
    qg = q.reshape(B, S, KV, rep, D)
    quantized = ksl is not None
    ckc = ck.astype(q.dtype) if quantized else ck
    cvc = cv.astype(q.dtype) if quantized else cv
    scores = jnp.einsum("bsgrd,btgd->bgrst", qg, ckc).astype(jnp.float32)
    if quantized:
        scores = scores * jnp.transpose(ksl, (0, 2, 1))[:, :, None, None, :] \
            / math.sqrt(D)
    else:
        scores = scores / math.sqrt(D)
    mask = (jnp.arange(L)[None, None, :] <=
            qpos[:, :, None])[:, None, None, :, :]
    scores = jnp.where(mask, scores, NEG_INF)
    p = jax.nn.softmax(scores, -1).astype(q.dtype)
    if quantized:
        p = p * jnp.transpose(vsl, (0, 2, 1))[:, :, None, None, :].astype(
            p.dtype)
    out = jnp.einsum("bgrst,btgd->bsgrd", p, cvc)
    return out.reshape(B, S, H, D)


def _try_pallas(q, k_pool, v_pool, tables, pos, ks=None, vs=None, window=0,
                ring=0, head_major=False):
    """Kernel selection (``select.select_paged_attention``, decided from
    platform, partitioning and static shapes before the kernel traces):
    returns the Pallas result when the kernel is selected, else None
    (caller runs the jnp reference). A selected kernel that fails to
    lower or compile raises."""
    from .select import XLA, record, select_paged_attention

    op = ("paged_window_attention" if window else
          "paged_attention_q" if ks is not None else "paged_attention")
    if record(op, select_paged_attention(
            q.shape, k_pool.shape, head_major=head_major)) == XLA:
        return None
    from . import paged_attention_pallas as pk

    if ks is None:
        return pk.paged_attention(q, k_pool, v_pool, tables, pos,
                                  window=window, ring=ring,
                                  head_major=head_major)
    return pk.paged_attention_q(q, k_pool, ks, v_pool, vs, tables, pos)


def paged_verify_attention(q, k_pool, v_pool, block_tables, pos,
                           head_major=False):
    """Multi-token verify attention through block tables (GQA-native) —
    the decode window generalized from 1 to W positions.

    q: (B, W, H, D) rope'd queries at positions ``pos[b] + arange(W)``;
    pools: (N, bs, KV, D), or (N, KV, bs, D) ``head_major``;
    block_tables: (B, M); pos: int32 (B,) window
    start per row (the window's K/V must already be written at
    ``pos..pos+W-1``, :func:`write_window_kv`). IN-WINDOW CAUSAL MASK:
    query j attends context positions ``<= pos[b] + j`` — earlier window
    tokens are visible, later ones (and any stale rejected K/V beyond the
    window) are not. W = 1 reduces exactly to single-token decode.
    Dispatches to the Pallas kernel (``use_pallas()``); the jnp reference
    keeps scratch-block-0 masking — zeroed table rows write and read only
    scratch — and the same grouped einsum / fp32-softmax as the dense
    ``LlamaAttention.decode`` vector-pos path so greedy speculative output
    is token-exact vs the dense server.
    """
    W = q.shape[1]
    bt = block_tables if block_tables.ndim == 2 else block_tables[None]
    out = _try_pallas(q, k_pool, v_pool, bt, pos, head_major=head_major)
    if out is not None:
        return out
    ck = gather_block_kv(k_pool, bt, head_major)  # (B, L, KV, D)
    cv = gather_block_kv(v_pool, bt, head_major)
    qpos = pos[:, None] + jnp.arange(W)[None, :]  # (B, W)
    return _attention_core(q, ck, cv, qpos)


def paged_decode_attention(q, k_pool, v_pool, block_tables, pos,
                           head_major=False):
    """Single-token decode attention — :func:`paged_verify_attention` at
    W = 1 (mask ``arange(L) <= pos + 0`` is the plain ``<= pos``).
    q: (B, 1, H, D)."""
    return paged_verify_attention(q, k_pool, v_pool, block_tables, pos,
                                  head_major)


def paged_prefill_attention_in_rows(q, k_pool, v_pool, block_table, start,
                                    rows, head_major=False):
    """:func:`paged_prefill_attention` of a chunk q (C, H, D) in calls of
    ``rows`` query rows: the kernel holds a call's query rows (times the
    query heads a KV head) whole in VMEM, and a chunk of 256 rows at 32
    query heads of 128 does not fit (the compiler refuses it).
    Returns (C, H, D)."""
    return jnp.concatenate([
        paged_prefill_attention(q[None, s:s + rows], k_pool, v_pool,
                                block_table, start + s, head_major)[0]
        for s in range(0, q.shape[0], rows)])


def paged_prefill_attention(q, k_pool, v_pool, block_table, start,
                            head_major=False):
    """Chunked-prefill attention: one chunk of queries against ALL paged
    context written so far (earlier chunks + shared prefix blocks) plus
    the causal part of the chunk itself.

    q: (1, C, H, D) rope'd queries at positions ``start + arange(C)``;
    block_table: (M,) single request row; the chunk's K/V must already be
    scattered into the pool (``write_chunk_kv``). Key positions beyond a
    query's position are masked, so right-pad garbage in the final chunk
    and unallocated (scratch) table entries never reach a real query.
    Prefill is the verify kernel at B=1, W=C, pos=[start].
    """
    C = q.shape[1]
    bt = block_table if block_table.ndim == 2 else block_table[None]
    start_v = jnp.full((1,), start, jnp.int32)
    out = _try_pallas(q, k_pool, v_pool, bt, start_v, head_major=head_major)
    if out is not None:
        return out
    ck = gather_block_kv(k_pool, bt, head_major)  # (1, L, KV, D)
    cv = gather_block_kv(v_pool, bt, head_major)
    qpos = (start + jnp.arange(C))[None, :]       # (1, C)
    return _attention_core(q, ck, cv, qpos)


# --------------------------------------------------------------------------- #
# Quantized pool (kv_quant="int8"): the pool stores int8 codes
# (num_blocks, bs, KV, D) plus one f32 scale per (block, kv-head)
# (num_blocks, KV) — symmetric absmax, value ≈ code · scale. Half the HBM
# bytes of bf16 (a quarter of f32), so ~2× resident blocks at the same pool
# budget AND ~2× less KV traffic per decode step. The attention twins below
# fold the dequant INTO the program — scale lands on the QK accumulator and
# on p before the V accumulation — so a full-precision pool is never
# materialized (XLA fuses the scale multiply into the surrounding einsum;
# a Pallas kernel would apply it on the VMEM tile). int8 codes (|q| ≤ 127)
# are exact in bf16/f32, so the only error is the quantization rounding.
# --------------------------------------------------------------------------- #

# ------------------------------------------------------------ sliding window
def ring_positions(ring_blocks, block_size, pos):
    """Absolute position held by every entry of a row's ring, given that the
    row is writing position ``pos``: logical block ``j`` lives in ring block
    ``j % ring_blocks``, so ring block ``r`` holds the newest ``j <=
    pos // bs`` with ``j % ring_blocks == r``. pos (B,) -> (B, ring*bs);
    entries of blocks not yet written come out negative."""
    cur = pos[:, None] // block_size                              # (B, 1)
    r = jnp.arange(ring_blocks)[None, :]
    j = cur - (cur - r) % ring_blocks                             # (B, ring)
    return (j[:, :, None] * block_size
            + jnp.arange(block_size)[None, None, :]).reshape(pos.shape[0], -1)


def write_ring_kv(k_pool, v_pool, k, v, ring_tables, pos, head_major=False):
    """Scatter ONE token's K/V per row into its window ring. k/v (B, KV,
    D); ring_tables (B, ring) block ids (all 0 = scratch for a masked row);
    pos (B,)."""
    bs = k_pool.shape[2 if head_major else 1]
    ring = ring_tables.shape[1]
    bid = jnp.take_along_axis(ring_tables, ((pos // bs) % ring)[:, None],
                              axis=1)[:, 0]
    return _set_tokens(k_pool, v_pool, k, v, bid, pos % bs, head_major)


def paged_window_attention(q, k_pool, v_pool, ring_tables, pos, window,
                           head_major=False):
    """Single-token decode attention over the last ``window`` positions,
    read from a per-row RING of blocks (the token's own K/V already written,
    :func:`write_ring_kv`). q (B, 1, H, D); ring_tables (B, ring). The
    Pallas kernel starts its walk at the window's first block; the jnp
    reference gathers the whole ring and masks by the position each entry
    holds."""
    out = _try_pallas(q, k_pool, v_pool, ring_tables, pos, window=window,
                      ring=ring_tables.shape[1], head_major=head_major)
    if out is not None:
        return out
    B, _, H, D = q.shape
    ck = gather_block_kv(k_pool, ring_tables, head_major)  # (B, ring*bs, ..)
    cv = gather_block_kv(v_pool, ring_tables, head_major)
    bs, KV = ck.shape[1] // ring_tables.shape[1], ck.shape[2]
    kpos = ring_positions(ring_tables.shape[1], bs, pos)
    mask = ((kpos >= 0) & (kpos <= pos[:, None])
            & (kpos > pos[:, None] - window))       # (B, L)
    qg = q.reshape(B, KV, H // KV, D)
    scores = jnp.einsum("bgrd,btgd->bgrt", qg, ck).astype(jnp.float32) \
        / math.sqrt(D)
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, -1).astype(q.dtype)
    return jnp.einsum("bgrt,btgd->bgrd", p, cv).reshape(B, 1, H, D)


def slot_ring_tables(rows, ring_blocks):
    """Block ids of each row's window ring: slot ``b`` owns blocks ``1 +
    b*R .. (b+1)*R`` of every window layer's ring pool (block 0 is scratch;
    inference/executor.py lays the pools out so). rows (B,) -> (B, R)."""
    return 1 + rows[:, None] * ring_blocks + jnp.arange(ring_blocks)[None, :]


def masked_attention(q, k, v, mask):
    """q (B, C, H, D); k, v (B, T, KV, D); mask (B, C, T) -> (B, C, H, D);
    float32 scores and softmax, ``1/sqrt(D)``: attention under any mask, for
    the callers whose keys are not a causal run of positions."""
    B, C, H, D = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, C, KV, H // KV, D)
    s = jnp.einsum("bcgrd,btgd->bgrct", qg, k).astype(jnp.float32) \
        / math.sqrt(D)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, -1).astype(q.dtype)
    return jnp.einsum("bgrct,btgd->bcgrd", p, v).reshape(B, C, H, D)


def window_chunk_attention(q, k, v, k_pool, v_pool, ring, start, n_valid,
                           window, head_major=False):
    """One prompt chunk over a window layer's ring, then its write: q (1, C,
    H, D) at positions ``start + arange(C)`` (``start`` block-aligned), k, v
    (C, KV, D) the chunk's own, ``ring`` (R,) the slot's ring block ids, the
    first ``n_valid`` rows real. The ring holds the window and one block,
    less than the window plus a chunk, so the chunk attends the ring's past
    (the ``ceil(window / bs)`` blocks before its first) and its own K/V
    side by side, and only then overwrites the ring. Returns (out (1, C, H,
    D), k_pool, v_pool)."""
    from .select import XLA, record

    record("window_chunk_attention", XLA)
    bs = k_pool.shape[2 if head_major else 1]
    C = q.shape[1]
    R = ring.shape[0]
    nb = -(-window // bs)
    jj = start // bs - nb + jnp.arange(nb)
    ids = ring[jj % R]
    past_pos = (jj[:, None] * bs + jnp.arange(bs)[None]).reshape(-1)
    keys = jnp.concatenate([gather_block_kv(k_pool, ids, head_major)[0], k])
    vals = jnp.concatenate([gather_block_kv(v_pool, ids, head_major)[0], v])
    qpos = start + jnp.arange(C)
    kpos = jnp.concatenate([past_pos, qpos])
    mask = ((kpos[None] >= 0) & (kpos[None] <= qpos[:, None])
            & (kpos[None] > qpos[:, None] - window))
    o = masked_attention(q, keys[None], vals[None], mask[None])
    valid = jnp.arange(C) < n_valid
    bid = jnp.where(valid, ring[(qpos // bs) % R], 0)
    k_pool, v_pool = _set_tokens(k_pool, v_pool, k, v, bid, qpos % bs,
                                 head_major)
    return o, k_pool, v_pool


_QEPS = 1e-8   # scale floor: an all-zero block quantizes to scale ~0 with
               # zero codes instead of dividing by zero


def quantize_block_kv(x):
    """(N, bs, KV, D) float → ((N, bs, KV, D) int8, (N, KV) f32 scale);
    symmetric absmax per block per kv head."""
    xf = jnp.asarray(x).astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=(1, 3))                  # (N, KV)
    scale = jnp.maximum(absmax, _QEPS) / 127.0
    q = jnp.clip(jnp.round(xf / scale[:, None, :, None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_block_kv(q, scale):
    """Inverse of :func:`quantize_block_kv` — TEST/reference helper only;
    the serving programs never materialize this."""
    return q.astype(jnp.float32) * scale[:, None, :, None]


def _insert_token_q(qpool, scales, tok, bid, off):
    """Insert one token's K (or V) (B, KV, D) at slot ``off`` of block
    ``bid`` per row, requantizing each touched block in ONE pass: the block
    is reconstructed (codes · scale), the token dropped in, the per-head
    absmax recomputed and the whole block re-coded. When the new token
    does not move a head's absmax the scale is unchanged and old codes
    round-trip exactly (round(q·s/s) == q); only a scale-raising outlier
    re-rounds its block, bounding the error at scale/2 per value."""
    B = tok.shape[0]
    rows = jnp.arange(B)
    blk = qpool[bid].astype(jnp.float32) * \
        scales[bid][:, None, :, None]                   # (B, bs, KV, D)
    blk = blk.at[rows, off].set(tok.astype(jnp.float32))
    amax = jnp.max(jnp.abs(blk), axis=(1, 3))           # (B, KV)
    ns = jnp.maximum(amax, _QEPS) / 127.0
    q = jnp.clip(jnp.round(blk / ns[:, None, :, None]), -127,
                 127).astype(jnp.int8)
    # duplicate bids only occur for masked rows parked on scratch block 0
    # (whichever row wins, the content stays finite and is never attended)
    return qpool.at[bid].set(q), scales.at[bid].set(ns)


def write_window_kv_q(kq, ks, vq, vs, k, v, block_tables, pos):
    """Quantizing twin of :func:`write_window_kv`: scatter a WINDOW of new
    tokens' K/V into the int8 pool, rescaling each touched block in one
    pass per token. k/v: (B, W, KV, D) float; kq/vq: (N, bs, KV, D) int8;
    ks/vs: (N, KV) f32. W is small and static (1 = decode, k+1 = verify
    window), so the per-token walk unrolls at trace time — a Pallas kernel
    would fold the whole window into one block pass."""
    bs = kq.shape[1]
    W = k.shape[1]
    for j in range(W):
        pj = pos + j
        bid = jnp.take_along_axis(block_tables, (pj // bs)[:, None],
                                  axis=1)[:, 0]
        off = pj % bs
        kq, ks = _insert_token_q(kq, ks, k[:, j], bid, off)
        vq, vs = _insert_token_q(vq, vs, v[:, j], bid, off)
    return kq, ks, vq, vs


def write_decode_kv_q(kq, ks, vq, vs, k, v, block_tables, pos):
    """Quantizing twin of :func:`write_decode_kv` — one token per row.
    k/v: (B, KV, D)."""
    return write_window_kv_q(kq, ks, vq, vs, k[:, None], v[:, None],
                             block_tables, pos)


def write_chunk_kv_q(kq, ks, vq, vs, k, v, block_table, start):
    """Quantizing twin of :func:`write_chunk_kv`: a prefill chunk fully
    overwrites its blocks, so each block is quantized FRESH (no rescale
    pass). k/v: (C, KV, D), C a multiple of ``bs``."""
    bs = kq.shape[1]
    nb = k.shape[0] // bs
    blocks = jax.lax.dynamic_slice_in_dim(block_table, start // bs, nb, 0)
    knew, ksn = quantize_block_kv(k.reshape(nb, bs, *k.shape[1:]))
    vnew, vsn = quantize_block_kv(v.reshape(nb, bs, *v.shape[1:]))
    return (kq.at[blocks].set(knew), ks.at[blocks].set(ksn),
            vq.at[blocks].set(vnew), vs.at[blocks].set(vsn))


def gather_block_scales(scales, block_tables, block_size):
    """Per-TOKEN scale view of the per-block scales: (N, KV) gathered
    through (B, M) tables and repeated across the block → (B, L, KV),
    L = M·block_size — aligned with :func:`gather_block_kv`'s context."""
    bt = block_tables if block_tables.ndim == 2 else block_tables[None]
    return jnp.repeat(scales[bt], block_size, axis=1)


def paged_verify_attention_q(q, kq, ks, vq, vs, block_tables, pos):
    """Fused-dequant twin of :func:`paged_verify_attention`: attention
    reads int8 K/V codes and applies the per-block-per-head scales INSIDE
    the program (see :func:`_attention_core`) — never materializing a
    dequantized pool. Masking / softmax semantics are identical to the fp
    twin. The Pallas kernel applies the same scales on the VMEM tile."""
    W = q.shape[1]
    bs = kq.shape[1]
    bt = block_tables if block_tables.ndim == 2 else block_tables[None]
    out = _try_pallas(q, kq, vq, bt, pos, ks=ks, vs=vs)
    if out is not None:
        return out
    ckq = gather_block_kv(kq, bt)                 # (B, L, KV, D) int8
    cvq = gather_block_kv(vq, bt)
    ksl = gather_block_scales(ks, bt, bs)         # (B, L, KV) f32
    vsl = gather_block_scales(vs, bt, bs)
    qpos = pos[:, None] + jnp.arange(W)[None, :]  # (B, W)
    return _attention_core(q, ckq, cvq, qpos, ksl=ksl, vsl=vsl)


def paged_decode_attention_q(q, kq, ks, vq, vs, block_tables, pos):
    """Single-token fused-dequant decode — :func:`paged_verify_attention_q`
    at W = 1. q: (B, 1, H, D)."""
    return paged_verify_attention_q(q, kq, ks, vq, vs, block_tables, pos)


def paged_prefill_attention_q(q, kq, ks, vq, vs, block_table, start):
    """Fused-dequant twin of :func:`paged_prefill_attention` — one prefill
    chunk of queries against the quantized paged context (the verify
    kernel at B=1, W=C, pos=[start])."""
    C = q.shape[1]
    bs = kq.shape[1]
    bt = block_table if block_table.ndim == 2 else block_table[None]
    start_v = jnp.full((1,), start, jnp.int32)
    out = _try_pallas(q, kq, vq, bt, start_v, ks=ks, vs=vs)
    if out is not None:
        return out
    ckq = gather_block_kv(kq, bt)                 # (1, L, KV, D) int8
    cvq = gather_block_kv(vq, bt)
    ksl = gather_block_scales(ks, bt, bs)         # (1, L, KV) f32
    vsl = gather_block_scales(vs, bt, bs)
    qpos = (start + jnp.arange(C))[None, :]       # (1, C)
    return _attention_core(q, ckq, cvq, qpos, ksl=ksl, vsl=vsl)
