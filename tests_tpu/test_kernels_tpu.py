"""Compiled-Mosaic kernel correctness on a real chip.

Everything here runs the ACTUAL Pallas kernels (no PT_FLASH_INTERPRET), so
BlockSpec index maps, VMEM scratch carries, and the GQA head-group mapping
are exercised as compiled code.  References are plain jnp math in float32.
Cases either call a kernel function directly or call the public op and then
assert (``took``) that the selection rule traced the Pallas kernel — a case
can no longer pass on the jnp composition without saying so.

Tolerances are bf16-realistic: flash outputs compare at ~2e-2 after the
f32 reference is cast through bf16 inputs.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import select


@pytest.fixture(autouse=True)
def _fresh_selection_counter():
    select.selected(reset=True)
    yield


def took(op, impl="pallas"):
    """Assert ``op`` traced under ``impl`` (and nothing else) in this case."""
    got = select.selected().get(op, {})
    assert set(got) == {impl}, f"{op} traced under {got}, expected {impl}"


B, H, KV, D = 2, 8, 4, 128
S = 1024


def _qkv(seed, s=S, kv=KV, dtype=jnp.bfloat16):
    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(B, H, s, D).astype("float32")).astype(dtype)
    k = jnp.asarray(rng.randn(B, kv, s, D).astype("float32")).astype(dtype)
    v = jnp.asarray(rng.randn(B, kv, s, D).astype("float32")).astype(dtype)
    return q, k, v


def _ref(q, k, v, causal):
    """f32 dense reference with GQA K/V head repeat."""
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    if kf.shape[1] != qf.shape[1]:
        rep = qf.shape[1] // kf.shape[1]
        kf = jnp.repeat(kf, rep, axis=1)
        vf = jnp.repeat(vf, rep, axis=1)
    logits = jnp.einsum("bhsd,bhtd->bhst", qf, kf) / np.sqrt(D)
    if causal:
        s = logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, -1e30)
    return jnp.einsum("bhst,bhtd->bhsd", jax.nn.softmax(logits, -1), vf)


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_fwd_matches_dense_gqa(causal):
    from paddle_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv(0)
    out = jax.jit(lambda a, b, c: flash_attention(a, b, c, causal))(q, k, v)
    took("flash_attention")
    want = _ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_flash_bwd_matches_dense_grads():
    from paddle_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv(1)

    def loss_flash(a, b, c):
        return jnp.sum(flash_attention(a, b, c, True).astype(jnp.float32)
                       * 0.01)

    def loss_ref(a, b, c):
        return jnp.sum(_ref(a, b, c, True) * 0.01)

    g = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    took("flash_attention")
    gr = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(q, k, v)
    for got, want, name in zip(g, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   rtol=5e-2, atol=5e-2, err_msg=name)


def test_flash_long_sequence_streaming_grid():
    """S=8192 exercises the streaming grid (VMEM scratch carries across the
    KV loop) — values vs the dense f32 reference on a slice."""
    from paddle_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv(2, s=8192, kv=KV)
    out = jax.jit(lambda a, b, c: flash_attention(a, b, c, True))(q, k, v)
    assert bool(jnp.all(jnp.isfinite(out.astype(jnp.float32))))
    want = _ref(q[:, :, :1024], k[:, :, :1024], v[:, :, :1024], True)
    np.testing.assert_allclose(np.asarray(out[:, :, :1024], np.float32),
                               np.asarray(want), rtol=2e-2, atol=2e-2)


def test_fused_ce_matches_logits_ce():
    from paddle_tpu.ops.fused_ce import fused_linear_cross_entropy

    rng = np.random.RandomState(3)
    T, Hd, V = 512, 256, 4096
    h = jnp.asarray(rng.randn(T, Hd).astype("float32")).astype(jnp.bfloat16)
    w = jnp.asarray(rng.randn(Hd, V).astype("float32") * 0.02
                    ).astype(jnp.bfloat16)
    labels = jnp.asarray(rng.randint(0, V, (T,)).astype("int64"))
    got = jax.jit(lambda a, b: fused_linear_cross_entropy(a, b, labels,
                                                          chunk_size=128)
                  )(h, w)
    logits = (h.astype(jnp.float32) @ w.astype(jnp.float32))
    lse = jax.scipy.special.logsumexp(logits, -1)
    gold = jnp.take_along_axis(logits, labels[:, None].astype(jnp.int32),
                               -1)[:, 0]
    want = jnp.mean(lse - gold)
    np.testing.assert_allclose(float(got), float(want), rtol=2e-3)


@pytest.mark.parametrize("rows,width,dtype", [
    (64, 1024, jnp.float32),
    (8192, 4096, jnp.bfloat16),      # hidden 4096: the tile is 256 rows
    (8192, 4096, jnp.float32),       # ... and 128 in f32
    (8, 4096, jnp.bfloat16),         # decode rows
], ids=["f32_64x1024", "bf16_8192x4096", "f32_8192x4096", "bf16_8x4096"])
def test_fused_norm_kernels_match_reference(rows, width, dtype):
    """The KERNELS (not the wrappers, which may legitimately select jnp)
    against the f32 formula, at hidden 4096 where the old fixed 512-row
    tile was refused for scoped VMEM."""
    from paddle_tpu.ops.fused_norm import _ln_pallas, _rms_pallas

    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(rows, width).astype("float32")).astype(dtype)
    wgt = jnp.asarray(rng.randn(width).astype("float32")).astype(dtype)
    bias = jnp.asarray(rng.randn(width).astype("float32")).astype(dtype)
    xf, wf, bf = (a.astype(jnp.float32) for a in (x, wgt, bias))
    tol = 1e-4 if dtype == jnp.float32 else 3e-2

    got = jax.jit(lambda a, w: _rms_pallas(a, w, 1e-6))(x, wgt)
    want = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + 1e-6) \
        * wf
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)

    got = jax.jit(lambda a, w, b: _ln_pallas(a, w, b, 1e-5))(x, wgt, bias)
    mu = jnp.mean(xf, -1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, -1, keepdims=True)
    want = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * wf + bf
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want),
                               rtol=tol, atol=tol)


def test_fused_norm_wrappers_select_the_kernel():
    from paddle_tpu.ops.fused_norm import fused_layer_norm, fused_rms_norm

    x = jnp.ones((512, 4096), jnp.bfloat16)
    w = jnp.ones((4096,), jnp.bfloat16)
    jax.jit(lambda a, b: fused_rms_norm(a, b))(x, w).block_until_ready()
    jax.jit(lambda a, b: fused_layer_norm(a, b, b))(x, w).block_until_ready()
    took("fused_norm")


# Llama-3-8B attention geometry: the shapes GenerationServer(cache="paged")
# serves on the chip
_PH, _PKV, _PD = 32, 8, 128


def _paged_setup(B, W, bs, seed, quantized):
    """Random pool + per-row block tables with distinct blocks; returns the
    kernel operands and the f32 jnp reference output."""
    from paddle_tpu.ops import paged_attention as pa

    rng = np.random.RandomState(seed)
    N, M = 96, 10
    q = jnp.asarray(rng.randn(B, W, _PH, _PD).astype("float32")
                    ).astype(jnp.bfloat16)
    kf = jnp.asarray(rng.randn(N, bs, _PKV, _PD).astype("float32"))
    vf = jnp.asarray(rng.randn(N, bs, _PKV, _PD).astype("float32"))
    tables = jnp.asarray(
        rng.permutation(np.arange(1, N))[:B * M].reshape(B, M)
        .astype("int32"))
    # window start per row, leaving room for the W-token window
    pos = jnp.asarray(rng.randint(bs, M * bs - W, (B,)).astype("int32"))
    qpos = pos[:, None] + jnp.arange(W)[None, :]
    if quantized:
        kq, ks = pa.quantize_block_kv(kf)
        vq, vs = pa.quantize_block_kv(vf)
        kref, vref = pa.dequantize_block_kv(kq, ks), \
            pa.dequantize_block_kv(vq, vs)
        pools = (kq, ks, vq, vs)
    else:
        kb, vb = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
        kref, vref = kb.astype(jnp.float32), vb.astype(jnp.float32)
        pools = (kb, vb)
    want = pa._attention_core(q.astype(jnp.float32),
                              pa.gather_block_kv(kref, tables),
                              pa.gather_block_kv(vref, tables), qpos)
    return q, pools, tables, pos, want


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("B,W", [(8, 1), (8, 4), (1, 128)],
                         ids=["decode_W1", "verify_W4", "prefill_C128"])
def test_paged_attention_kernel_matches_f32_reference(B, W, quantized):
    """``paged_attention`` / ``paged_attention_q`` as compiled Mosaic at
    head_dim 128, GQA 32/8 — decode, verify window and a prefill chunk."""
    from paddle_tpu.ops import paged_attention_pallas as pk

    bs = 32 if quantized else 16
    q, pools, tables, pos, want = _paged_setup(B, W, bs, 11 + W, quantized)
    if quantized:
        kq, ks, vq, vs = pools
        got = jax.jit(pk.paged_attention_q)(q, kq, ks, vq, vs, tables, pos)
    else:
        got = jax.jit(pk.paged_attention)(q, *pools, tables, pos)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=3e-2, atol=3e-2)


def _cell_setup(B, W, quantized, seed):
    """The serving cells' own shapes (benchmarks/configs): a 4096-block
    pool, tables 4096 tokens wide, ragged rows — position 0, either side
    of a block and of a 256-token group boundary, the full table width —
    idle rows (zero table, position 0) and table tails that point at a
    NaN block (fp; NaN scales for int8), which must not be read."""
    from paddle_tpu.ops import paged_attention as pa

    rng = np.random.RandomState(seed)
    N, bs = 4096, 32 if quantized else 16
    M = 4096 // bs
    edges = [0, 15, 16, 255, 256, 257, 4096 - W]
    if B == 1:
        pos = np.array([4096 - 2 * W], np.int32)      # a late chunk
        idle = 0
    else:
        idle = 8
        pos = np.concatenate([
            np.minimum(edges, 4096 - W),
            rng.randint(64, 1400, B - idle - len(edges)),
            np.zeros(idle, int)]).astype(np.int32)
    dead = N - 1
    tables = np.full((B, M), dead, np.int32)
    free, took = rng.permutation(np.arange(1, dead)), 0
    for b in range(B):
        nb = (pos[b] + W - 1) // bs + 1
        tables[b, :nb] = free[took:took + nb]
        took += nb
    tables[B - idle:] = 0
    q = jnp.asarray(rng.randn(B, W, _PH, _PD).astype("float32")
                    ).astype(jnp.bfloat16)
    kf = rng.randn(N, bs, _PKV, _PD).astype("float32")
    vf = rng.randn(N, bs, _PKV, _PD).astype("float32")
    kf[[0, dead]] = vf[[0, dead]] = 0.0
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)
    qpos = pos[:, None] + jnp.arange(W)[None, :]
    if quantized:
        kq, ks = pa.quantize_block_kv(jnp.asarray(kf))
        vq, vs = pa.quantize_block_kv(jnp.asarray(vf))
        kref, vref = pa.dequantize_block_kv(kq, ks), \
            pa.dequantize_block_kv(vq, vs)
        pools = (kq, ks.at[dead].set(jnp.nan), vq, vs.at[dead].set(jnp.nan))
    else:
        kb, vb = jnp.asarray(kf, jnp.bfloat16), jnp.asarray(vf, jnp.bfloat16)
        kref, vref = kb.astype(jnp.float32), vb.astype(jnp.float32)
        pools = (kb.at[dead].set(jnp.nan), vb.at[dead].set(jnp.nan))
    want = jax.jit(lambda q_, k_, v_: pa._attention_core(
        q_.astype(jnp.float32), pa.gather_block_kv(k_, tables),
        pa.gather_block_kv(v_, tables), qpos))(q, kref, vref)
    return q, pools, tables, pos, want


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("B,W", [(64, 1), (1, 128)],
                         ids=["decode_B64", "chunk_C128_late"])
def test_paged_attention_at_the_serving_cells_shapes(B, W, quantized):
    from paddle_tpu.ops import paged_attention_pallas as pk

    q, pools, tables, pos, want = _cell_setup(B, W, quantized, 5 + W)
    op = pk.paged_attention_q if quantized else pk.paged_attention
    got = np.asarray(jax.jit(op)(q, *pools, tables, pos), np.float32)
    assert np.isfinite(got).all()          # the NaN block was never read
    np.testing.assert_allclose(got, np.asarray(want), rtol=3e-2, atol=3e-2)


def test_paged_attention_public_ops_select_the_kernel():
    from paddle_tpu.ops import paged_attention as pa

    q, pools, tables, pos, want = _paged_setup(8, 1, 16, 3, False)
    got = jax.jit(pa.paged_decode_attention)(q, *pools, tables, pos)
    took("paged_attention")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=3e-2, atol=3e-2)
    q, pools, tables, pos, want = _paged_setup(8, 1, 32, 3, True)
    got = jax.jit(pa.paged_decode_attention_q)(q, *pools, tables, pos)
    took("paged_attention_q")
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=3e-2, atol=3e-2)


# --------------------------------------------------------------------------- #
# Phi-4-mini-flash-reasoning's kernels at its published widths: 40 q / 20 kv
# heads of 64 as 10 packed pairs of 128 in head-major blocks, window 512 over
# a ring of 33 blocks of 16, d_inner 5120 x d_state 16
# --------------------------------------------------------------------------- #
_FH, _FKV, _FD, _FW, _FBS = 40, 10, 128, 512, 16


def _pair_attention_f32(q, ck, cv, mask):
    """Differential attention's two softmaxes as the model's docstring has
    them, in float32, from UNPACKED 64-wide heads: q (B, W, 40, 64); ck, cv
    (B, L, 20, 64); mask (B, W, L). Returns each softmax applied to the
    128-wide value pair, (B, W, 20 pairs, 2 halves, 128): what the packed
    kernel rows return before the model takes their difference."""
    B, W = q.shape[:2]
    L, d, f = ck.shape[1], q.shape[-1], jnp.float32
    qh = q.astype(f).reshape(B, W, 10, 2, 2, d)     # kv pair, q pair, half
    kh = ck.astype(f).reshape(B, L, 10, 2, d)       # kv pair: k1, k2
    vv = cv.astype(f).reshape(B, L, 10, 2 * d)      # (v1 | v2)
    outs = []
    for half in range(2):
        sc = jnp.einsum("bwgrd,blgd->bgrwl", qh[:, :, :, :, half],
                        kh[:, :, :, half], precision="highest") / np.sqrt(d)
        sc = jnp.where(mask[:, None, None], sc, -1e30)
        outs.append(jnp.einsum("bgrwl,blge->bwgre", jax.nn.softmax(sc, -1),
                               vv, precision="highest"
                               ).reshape(B, W, 20, 2 * d))
    return jnp.stack(outs, 3)


def _pair_case(B, W, seed, window=0):
    """Head-major pools holding packed pairs, the model's own packed query
    rows, ragged positions; the reference works from the unpacked heads.
    Table entry j of a row holds the context's positions j*bs .. j*bs+bs-1
    (of a ring: the positions ``pa.ring_positions`` names)."""
    from paddle_tpu.models.phi4flash import _pack_q
    from paddle_tpu.ops import paged_attention as pa

    rng = np.random.RandomState(seed)
    ring = -(-_FW // _FBS) + 1 if window else 0
    M = ring or 256
    N, L = 1 + B * M, M * _FBS
    q64 = jnp.asarray(rng.randn(B, W, _FH * 64).astype("float32")
                      ).astype(jnp.bfloat16)
    if window:
        pos = np.array([0, 17, _FW - 1, _FW, 3 * _FW + 5,
                        40 * ring * _FBS + 3,
                        *rng.randint(_FW, 6000, B - 6)], np.int32)
    elif B == 1:
        pos = np.array([L - 2 * W], np.int32)             # a late chunk
    else:
        pos = np.array([0, 15, 16, 255, 256, 257, L - W,
                        *rng.randint(64, 3000, B - 7)], np.int32)
    tables = jnp.asarray(
        1 + np.arange(B)[:, None] * M + np.arange(M)[None, :], jnp.int32)
    ck = jnp.asarray(rng.randn(B, L, 20, 64).astype("float32"), jnp.bfloat16)
    cv = jnp.asarray(rng.randn(B, L, 20, 64).astype("float32"), jnp.bfloat16)

    def pool(c):
        blocks = c.reshape(B, M, _FBS, _FKV, _FD).swapaxes(2, 3)
        return jnp.zeros((N, _FKV, _FBS, _FD), jnp.bfloat16).at[tables].set(
            blocks)

    pos = jnp.asarray(pos)
    qpos = pos[:, None] + jnp.arange(W)[None, :]
    kpos = (pa.ring_positions(ring, _FBS, pos) if window
            else jnp.broadcast_to(jnp.arange(L)[None], (B, L)))[:, None, :]
    mask = (kpos >= 0) & (kpos <= qpos[:, :, None])
    if window:
        mask = mask & (kpos > qpos[:, :, None] - window)
    want = jax.jit(_pair_attention_f32)(q64.reshape(B, W, _FH, 64), ck, cv,
                                        mask)
    return _pack_q(q64, 64), pool(ck), pool(cv), tables, pos, want


@pytest.mark.parametrize("B,W", [(128, 1), (1, 128), (1, 1)],
                         ids=["decode_B128", "chunk_C128_late", "last_token"])
def test_packed_pair_attention_at_head_dim_64_matches_the_unpacked_math(B, W):
    """The differential pair at head_dim 64 through the SAME kernel: 10
    packed kv heads of 128 in head-major blocks, query rows (q1|0), (0|q2).
    Against the two softmaxes computed from the unpacked 64-wide heads."""
    from paddle_tpu.ops import paged_attention as pa

    q, kp, vp, tables, pos, want = _pair_case(B, W, 21 + W)
    op = pa.paged_decode_attention if W == 1 else pa.paged_verify_attention
    got = jax.jit(lambda *a: op(*a, head_major=True))(q, kp, vp, tables, pos)
    took("paged_attention")
    got = np.asarray(got, np.float32).reshape(B, W, 20, 2, 128)
    np.testing.assert_allclose(got, np.asarray(want), rtol=3e-2, atol=3e-2)


def test_window_walk_starts_at_the_windows_first_block():
    """128 rows over rings of 33 blocks, positions before, at and far past
    the window (rings that wrapped many times)."""
    from paddle_tpu.ops import paged_attention as pa

    q, kp, vp, tables, pos, want = _pair_case(128, 1, 33, window=_FW)
    got = jax.jit(lambda *a: pa.paged_window_attention(
        *a, _FW, head_major=True))(q, kp, vp, tables, pos)
    took("paged_window_attention")
    got = np.asarray(got, np.float32).reshape(128, 1, 20, 2, 128)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, np.asarray(want), rtol=3e-2, atol=3e-2)


def _scan_inputs(lead, seed, d=5120, S=16):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype("float32"))
    x, Bm, Cm = f(lead, d), f(lead, S), f(lead, S)
    dt = jnp.asarray(rng.uniform(1e-3, 0.2, (lead, d)).astype("float32"))
    A = -jnp.exp(f(S, d) * 0.3)
    return x, dt, A, Bm, Cm, f(d)


def _latent_cell_setup(B, W, seed):
    """The Mistral-Small-4 cell's shapes (benchmarks/configs): 128 slots,
    12,288-token tables in blocks of 64, rows of 256 + 64 values in three
    lane tiles, 32 heads. Ragged decode rows at the cell's contexts
    (1.6k-3.2k) beside the edges of the copy stream: position 0, either side
    of a block and of a 1,024-token group, one block beside the longest row,
    the first and the last row of the call. Every block no live entry names,
    and every dead table entry, is NaN."""
    from paddle_tpu.ops import latent_attention as la

    rng = np.random.RandomState(seed)
    bs, M, H, D, Dv = 64, 196, 32, 384, 256
    if B == 1:
        pos = np.array([2048 + 40], np.int32)       # a chunk mid-block
    else:
        edges = [0, 63, 64, 1023, 1024, 1025, 2047, 2048, 12287, 5]
        pos = np.concatenate([
            edges[:5], rng.randint(1600, 3200, B - len(edges)),
            edges[5:]]).astype(np.int32)
    nlive = (pos + W - 1) // bs + 1
    N = int(nlive.sum()) + 2
    dead = N - 1
    tables = np.full((B, M), dead, np.int32)
    free, took_ = rng.permutation(np.arange(1, dead)), 0
    for b in range(B):
        tables[b, :nlive[b]] = free[took_:took_ + nlive[b]]
        took_ += nlive[b]
    pool = rng.randn(N, bs, D).astype("float32")
    pool[..., 320:] = 0.0
    pool[[0, dead]] = 0.0
    q = rng.randn(B, W, H, D).astype("float32") * 0.3
    q[..., 320:] = 0.0
    q, pool = jnp.asarray(q, jnp.bfloat16), jnp.asarray(pool, jnp.bfloat16)
    tables, pos = jnp.asarray(tables), jnp.asarray(pos)
    want = jax.jit(lambda q_, p_: la._reference(
        q_.astype(jnp.float32), p_.astype(jnp.float32), tables, pos, Dv,
        0.1949, (0.1, 8192)))(q, pool)
    return q, pool.at[0].set(jnp.nan).at[dead].set(jnp.nan), tables, pos, want


@pytest.mark.parametrize("B,W", [(128, 1), (1, 256)],
                         ids=["decode_B128_ragged", "chunk_C256"])
def test_latent_attention_at_the_serving_cells_shapes(B, W):
    """One copy stream over all the rows of a call (PR 34): the compiled
    kernel against the jnp composition in float32."""
    from paddle_tpu.ops import latent_attention as la

    q, pool, tables, pos, want = _latent_cell_setup(B, W, 11 + W)
    got = jax.jit(lambda *a: la.latent_attention(
        *a, v_width=256, scale=0.1949, qscale=(0.1, 8192)))(
            q, pool, tables, pos)
    took("latent_attention")
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()          # no dead block was ever read
    np.testing.assert_allclose(got, np.asarray(want), rtol=3e-2, atol=3e-2)


def test_ssm_step_kernel_at_the_published_widths():
    """One token for 128 slots, d_inner 5120 x d_state 16, float32 state;
    a masked row (dt 0) keeps its state bit for bit."""
    from paddle_tpu.ops import selective_scan as ss

    x, dt, A, Bm, Cm, D = _scan_inputs(128, 7)
    h = jnp.asarray(np.random.RandomState(8).randn(128, 16, 5120)
                    .astype("float32"))
    dt = dt.at[5].set(0.0)
    want_y, want_h = ss.ssm_step_ref(x, dt, A, Bm, Cm, D, h)
    got_y, got_h = jax.jit(ss.ssm_step)(x, dt, A, Bm, Cm, D, h + 0.0)
    took("ssm_step")
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got_h[5]), np.asarray(h[5]))


def test_ssm_chunk_scan_kernel_at_the_published_widths():
    """A 128-token chunk from a state to a state against ``lax.scan``."""
    from paddle_tpu.ops import selective_scan as ss

    x, dt, A, Bm, Cm, D = _scan_inputs(128, 9)
    h0 = jnp.asarray(np.random.RandomState(10).randn(16, 5120)
                     .astype("float32"))
    dt = dt.at[100:].set(0.0)          # the chunk's padding keeps the state
    want_y, want_h = jax.jit(ss.ssm_chunk_scan_ref)(x, dt, A, Bm, Cm, D, h0)
    got_y, got_h = jax.jit(ss.ssm_chunk_scan)(x, dt, A, Bm, Cm, D, h0)
    took("ssm_chunk_scan")
    np.testing.assert_allclose(np.asarray(got_y), np.asarray(want_y),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-5, atol=1e-5)


def _ssm2_inputs(lead, seed, H=128, P=64, N=128):
    rng = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(rng.randn(*s).astype("float32"))
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (lead, H)).astype("float32"))
    A = -jnp.asarray(rng.uniform(1.0, 16.0, (H,)).astype("float32"))
    return f(lead, H, P), dt, A, f(lead, N), f(lead, N), f(H)


def test_ssm2_step_at_the_serving_cells_shapes():
    """One token for 96 slots of 128 heads x 64 x 128 float32 (4 MB a slot a
    layer: Granite 4.0-H), the served op — XLA's one fusion over the state
    — against the recurrence in numpy float64 on a few rows; a masked row
    (dt 0) keeps its state bit for bit."""
    from paddle_tpu.ops import ssm2

    x, dt, A, Bm, Cm, D = _ssm2_inputs(96, 11)
    h = jax.jit(lambda k: jax.random.normal(k, (96, 128, 64, 128),
                                            jnp.float32))(jax.random.key(12))
    dt = dt.at[5].set(0.0)
    got_y, got_h = jax.jit(ssm2.ssm2_step)(x, dt, A, Bm, Cm, D, h + 0.0)
    took("ssm2_step", "xla")
    f = lambda a: np.asarray(a, np.float64)
    for b in (0, 5, 95):
        want_h = (np.exp(f(dt[b]) * f(A))[:, None, None] * f(h[b])
                  + (f(dt[b])[:, None] * f(x[b]))[:, :, None]
                  * f(Bm[b])[None, None, :])
        want_y = (want_h * f(Cm[b])).sum(-1) + f(D)[:, None] * f(x[b])
        np.testing.assert_allclose(np.asarray(got_h[b]), want_h, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(got_y[b]), want_y, rtol=1e-4,
                                   atol=1e-4)
    np.testing.assert_array_equal(np.asarray(got_h[5]), np.asarray(h[5]))


def test_ssd_chunk_at_the_serving_cells_shapes():
    """A 256-token chunk of one slot from a NON-zero state, the last 56
    tokens padding, in the chunked form against the sequential recurrence:
    the state agrees as float32 does (the products run at ``highest``)."""
    from paddle_tpu.ops import ssm2

    x, dt, A, Bm, Cm, D = _ssm2_inputs(256, 13)
    h0 = jnp.asarray(np.random.RandomState(14).randn(128, 64, 128)
                     .astype("float32"))
    dt = dt.at[200:].set(0.0)
    want_y, want_h = jax.jit(ssm2.ssd_chunk_ref)(x, dt, A, Bm, Cm, D, h0)
    got_y, got_h = jax.jit(ssm2.ssd_chunk)(x, dt, A, Bm, Cm, D, h0)
    took("ssd_chunk", "xla")
    np.testing.assert_allclose(np.asarray(got_y[:200]),
                               np.asarray(want_y[:200]), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(got_h), np.asarray(want_h),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("rows", [960, 2560], ids=["tick", "chunk"])
@pytest.mark.parametrize("k,n", [(4096, 768), (768, 4096)],
                         ids=["gate_up", "down"])
def test_grouped_products_at_the_768_wide_experts(rows, k, n):
    """36 held experts of 4096 x 768, half of the pairs held (a tick's 96 x
    10 and a chunk's 256 x 10 pairs; 960 rows fill no whole row tile):
    megablox, as ``select_grouped_matmul`` chooses, against ragged_dot."""
    from paddle_tpu.ops import select
    from paddle_tpu.ops.grouped_matmul import grouped_matmul

    rng = np.random.RandomState(15)
    x = jnp.asarray(rng.randn(rows, k).astype("float32")).astype(jnp.bfloat16)
    w = jax.jit(lambda key: (jax.random.normal(key, (36, k, n), jnp.float32)
                             * 0.02).astype(jnp.bfloat16))(jax.random.key(16))
    sizes = rng.multinomial(rows // 2, [1 / 36] * 36).astype("int32")
    sizes[7] = 0                                   # an expert with no row
    held = int(sizes.sum())
    g = jnp.asarray(sizes)
    got = jax.jit(lambda x, w, g: grouped_matmul(x, w, g))(x, w, g)
    took("expert_gmm", select.GROUPED_MATMUL_ON_TPU)
    want = jax.lax.ragged_dot(x, w, g, preferred_element_type=jnp.float32)
    assert got.shape == (rows, n)
    np.testing.assert_allclose(np.asarray(got[:held]),
                               np.asarray(want[:held]), rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("B,S,IN,OUT", [(8, 1, 4096, 4096),
                                        (8, 1, 4096, 14336),
                                        (1, 128, 4096, 4096)],
                         ids=["decode_qo", "decode_up", "prefill_qo"])
def test_fused_lora_matmul_matches_f32_reference(B, S, IN, OUT):
    from paddle_tpu.ops.paged_attention_pallas import fused_lora_matmul

    rng = np.random.RandomState(21)
    R = 16
    x = jnp.asarray(rng.randn(B, S, IN).astype("float32")
                    ).astype(jnp.bfloat16)
    w = jnp.asarray(rng.randn(IN, OUT).astype("float32") * 0.02
                    ).astype(jnp.bfloat16)
    a = jnp.asarray(rng.randn(B, IN, R).astype("float32") * 0.02)
    b = jnp.asarray(rng.randn(B, R, OUT).astype("float32") * 0.02)
    s = jnp.asarray(rng.rand(B).astype("float32"))
    s = s.at[0].set(0.0)                       # a null-adapter row
    got = jax.jit(fused_lora_matmul)(x, w, a, b, s)
    xf, wf = x.astype(jnp.float32), w.astype(jnp.float32)
    want = xf @ wf + jnp.einsum("bsr,bro->bso",
                                jnp.einsum("bsh,bhr->bsr", xf, a), b) \
        * s[:, None, None]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=3e-2, atol=3e-2)


def test_int8_dequant_matmul_close_to_float():
    from paddle_tpu.ops.int8 import quantize_per_channel, w8_matmul

    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(32, 512).astype("float32")).astype(jnp.bfloat16)
    w = jnp.asarray(rng.randn(512, 1024).astype("float32") * 0.05)
    wq, scale = quantize_per_channel(w)
    assert wq.dtype == jnp.int8
    got = jax.jit(w8_matmul)(x, wq, scale)
    took("w8_matmul", "xla")        # M = 32 > 16: the dequantize-once path
    select.selected(reset=True)
    got8 = jax.jit(w8_matmul)(x[:8], wq, scale)
    took("w8_matmul")               # decode-shaped: the streaming kernel
    np.testing.assert_allclose(np.asarray(got8, np.float32),
                               np.asarray(got[:8], np.float32),
                               rtol=2e-2, atol=2e-2)
    want = x.astype(jnp.float32) @ w
    err = np.abs(np.asarray(got, np.float32) - np.asarray(want))
    rel = err.mean() / np.abs(np.asarray(want)).mean()
    assert rel < 2e-2, rel


def test_tiny_train_step_bf16_loss_decreases():
    """End-to-end train-step smoke on the chip: flash + fused CE under jit,
    AdamW, loss decreasing over 3 steps."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine

    cfg = LlamaConfig(vocab_size=2048, hidden_size=256, intermediate_size=704,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=512,
                      dtype="bfloat16", use_flash_attention=True)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    eng = ParallelEngine(model, optimizer=opt, loss_fn=None)
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (4, 512))
                           .astype("int32"))
    lbl = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (4, 512))
                           .astype("int64"))
    losses = [float(np.asarray(eng.train_batch(ids, lbl).value))
              for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    took("flash_attention")
    took("fused_norm")


def test_decode_generate_bf16_and_int8():
    """Compiled scan decode on the chip: greedy generate with bf16 weights,
    then the weight-only int8 path (Pallas dequant matmul) — same argmax
    tokens at temperature 0."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig(vocab_size=1024, hidden_size=256, intermediate_size=704,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=256,
                      dtype="bfloat16", use_flash_attention=True)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(0)
    prompt = paddle.to_tensor(rng.randint(0, cfg.vocab_size, (2, 16))
                              .astype("int32"))
    out_bf16 = np.asarray(model.generate(prompt, max_new_tokens=16,
                                         temperature=0.0).value)
    assert out_bf16.shape[1] >= 16

    model.quantize_int8()
    out_int8 = np.asarray(model.generate(prompt, max_new_tokens=16,
                                         temperature=0.0).value)
    # int8 rounding can flip rare near-ties; demand strong agreement
    agree = (out_bf16 == out_int8).mean()
    assert agree > 0.8, agree


def test_fused_adamw_kernel_matches_reference(monkeypatch):
    """The opt-in fused AdamW Pallas kernel as compiled Mosaic vs the XLA
    reference math (it ships default-off — see ops/fused_adamw.py for the
    measured overlap story — but must stay numerically correct on-chip)."""
    monkeypatch.setenv("PT_FUSED_ADAMW", "1")
    from paddle_tpu.ops import fused_adamw as fa

    rng = np.random.RandomState(0)
    K, N = 256, 1024
    p = jnp.asarray(rng.randn(K, N), dtype=jnp.bfloat16)
    g = jnp.asarray(rng.randn(K, N).astype("float32"))
    m = jnp.asarray(rng.randn(K, N).astype("float32"))
    v = jnp.asarray(np.abs(rng.randn(K, N)).astype("float32"))
    hp = dict(lr=1e-3, step=7, b1=0.9, b2=0.999, eps=1e-8, decay=0.01)

    assert fa.usable(p.shape), "kernel should engage on a single-chip TPU"
    got = fa.fused_adamw_update(p, g, m, v, **hp)
    nm, m2, v2 = fa._reference_update(p.astype(jnp.float32), g, m, v,
                                      hp["lr"], hp["b1"], hp["b2"],
                                      hp["eps"], hp["decay"], hp["step"])
    # the kernel multiplies by the precomputed 1/(1-b**step) while the
    # reference divides — a 1-ulp f32 difference that can flip bf16
    # rounding on a handful of elements; one bf16 ulp is the contract
    np.testing.assert_allclose(np.asarray(got[0], np.float32),
                               np.asarray(nm.astype(p.dtype), np.float32),
                               rtol=8e-3, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(m2),
                               rtol=2e-5, atol=2e-6)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(v2),
                               rtol=2e-5, atol=2e-6)


def test_flash_bwd_streaming_grid_s16384():
    """S=16384 BACKWARD through the streaming split kernels (the round-3
    tier only covered the forward at this length). Causality + a dO that is
    nonzero only on the first 1024 query rows make the true grads exactly
    computable from a 1024-dense reference: dq[:1024] matches it, and
    dk/dv beyond the first 1024 keys must be ZERO — while the real
    1024x1024 streaming grid still executes over the full length."""
    from paddle_tpu.ops.flash_attention import flash_attention

    s16 = 16384
    q, k, v = _qkv(7, s=s16, kv=2)
    q = q[:1, :4]
    k = k[:1]
    v = v[:1]

    def loss_flash(a, b, c):
        out = flash_attention(a, b, c, True).astype(jnp.float32)
        return jnp.sum(out[:, :, :1024] * 0.01)

    dq, dk, dv = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)

    def loss_ref(a, b, c):
        return jnp.sum(_ref(a, b, c, True) * 0.01)

    rq, rk, rv = jax.jit(jax.grad(loss_ref, argnums=(0, 1, 2)))(
        q[:, :, :1024], k[:, :, :1024], v[:, :, :1024])
    np.testing.assert_allclose(np.asarray(dq[:, :, :1024], np.float32),
                               np.asarray(rq, np.float32),
                               rtol=5e-2, atol=5e-2, err_msg="dq prefix")
    np.testing.assert_allclose(np.asarray(dk[:, :, :1024], np.float32),
                               np.asarray(rk, np.float32),
                               rtol=5e-2, atol=5e-2, err_msg="dk prefix")
    np.testing.assert_allclose(np.asarray(dv[:, :, :1024], np.float32),
                               np.asarray(rv, np.float32),
                               rtol=5e-2, atol=5e-2, err_msg="dv prefix")
    # zero-dO rows contribute nothing past the prefix
    assert float(jnp.max(jnp.abs(dk[:, :, 1024:].astype(jnp.float32)))) == 0.0
    assert float(jnp.max(jnp.abs(dv[:, :, 1024:].astype(jnp.float32)))) == 0.0
    assert float(jnp.max(jnp.abs(dq[:, :, 1024:].astype(jnp.float32)))) == 0.0


def test_fused_transformer_layer_on_chip():
    """incubate FusedTransformerEncoderLayer (fused qkv matmul + flash SDPA
    + fused norms) compiled bf16 on chip vs a plain f32 jnp re-derivation
    from the same weights (round-3 weak item: no on-chip fused-transformer
    case)."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn import FusedTransformerEncoderLayer

    d, heads, ffn = 256, 8, 512
    paddle.seed(11)
    layer = FusedTransformerEncoderLayer(d, heads, ffn, dropout_rate=0.0)
    layer.eval()
    rng = np.random.RandomState(5)
    x = rng.randn(2, 512, d).astype("float32") * 0.1

    out = np.asarray(layer(paddle.to_tensor(x)).value, np.float32)

    # f32 reference from the layer's own weights
    g = {n: np.asarray(p.value, np.float32)
         for n, p in layer.named_parameters()}
    qkv = x @ g["fused_attn.qkv_weight"] + g["fused_attn.qkv_bias"]
    B, S = x.shape[:2]
    qkv = qkv.reshape(B, S, 3, heads, d // heads)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    logits = np.einsum("bshd,bthd->bhst", q, k) / np.sqrt(d // heads)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    att = np.einsum("bhst,bthd->bshd", p, v).reshape(B, S, d)
    att = att @ g["fused_attn.linear_weight"] + g["fused_attn.linear_bias"]
    h = x + att

    def ln(y, w, b):
        mu = y.mean(-1, keepdims=True)
        var = y.var(-1, keepdims=True)
        return (y - mu) / np.sqrt(var + 1e-5) * w + b

    h = ln(h, g["fused_attn.post_ln.weight"], g["fused_attn.post_ln.bias"])
    f = np.maximum(h @ g["ffn.linear1.weight"] + g["ffn.linear1.bias"], 0.0)
    f = f @ g["ffn.linear2.weight"] + g["ffn.linear2.bias"]
    want = ln(h + f, g["ffn.norm.weight"], g["ffn.norm.bias"])
    np.testing.assert_allclose(out, want, rtol=3e-2, atol=3e-2)


def test_offloaded_update_matches_in_hbm_engine():
    """The windowed/backward-ordered offload chain + grad accumulation
    (r5) must be a SCHEDULING change only: params after 2 steps match the
    plain in-HBM engine bit-for-bit on the same data (both paths run the
    same fused-AdamW math; only moment residency differs)."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine

    cfg = LlamaConfig(vocab_size=1024, hidden_size=256,
                      intermediate_size=704, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=256, dtype="bfloat16",
                      use_flash_attention=True)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (4, 256)).astype("int32")
    lbl = rng.randint(0, cfg.vocab_size, (4, 256)).astype("int64")

    def train(offload):
        paddle.seed(0)
        model = LlamaForCausalLM(cfg)
        opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
        eng = ParallelEngine(model, optimizer=opt, loss_fn=None,
                             offload_opt_state=offload)
        losses = [float(np.asarray(eng.train_batch(ids, lbl).value))
                  for _ in range(2)]
        return losses, {n: np.asarray(v) for n, v in eng.params.items()}

    l_ref, w_ref = train(offload=False)
    l_off, w_off = train(offload=True)
    np.testing.assert_allclose(l_off, l_ref, rtol=1e-5, atol=1e-6)
    for n in w_ref:
        np.testing.assert_array_equal(w_off[n], w_ref[n], err_msg=n)


def test_offload_grad_accum_on_chip():
    """grad_accum composed with the offload chain on hardware: finite
    decreasing loss, moments stay in pinned_host."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine

    cfg = LlamaConfig(vocab_size=1024, hidden_size=256,
                      intermediate_size=704, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=256, dtype="bfloat16",
                      use_flash_attention=True)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    eng = ParallelEngine(model, optimizer=opt, loss_fn=None,
                         offload_opt_state=True, grad_accum=4)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (8, 256)).astype("int32")
    lbl = rng.randint(0, cfg.vocab_size, (8, 256)).astype("int64")
    losses = [float(np.asarray(eng.train_batch(ids, lbl).value))
              for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
    kinds = {v.sharding.memory_kind for slots in eng.opt_state.values()
             for v in slots.values()}
    assert kinds == {"pinned_host"}, kinds


def test_moe_llama_train_on_chip():
    """Model-level MoE (sparse dispatch + aux loss) as compiled Mosaic/XLA
    on hardware: finite decreasing loss over 3 steps."""
    import paddle_tpu as paddle
    from paddle_tpu.models import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.parallel import ParallelEngine

    cfg = LlamaConfig(vocab_size=2048, hidden_size=256,
                      intermediate_size=512, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=512, dtype="bfloat16",
                      use_flash_attention=True, moe_num_experts=4,
                      moe_top_k=2)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    opt = AdamW(learning_rate=1e-3, parameters=model.parameters())
    eng = ParallelEngine(model, optimizer=opt, loss_fn=None)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, (4, 512)).astype("int32")
    lbl = rng.randint(0, cfg.vocab_size, (4, 512)).astype("int64")
    losses = [float(np.asarray(eng.train_batch(ids, lbl).value))
              for _ in range(3)]
    assert all(np.isfinite(losses)) and losses[-1] < losses[0], losses
