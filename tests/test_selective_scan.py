"""ops/selective_scan.py and the sliding-window walk of the paged attention
kernel: the Pallas kernels (interpreted on the CPU) against their jnp
compositions, and the compositions against a recurrence written out by
hand."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_attention as pa
from paddle_tpu.ops import paged_attention_pallas as pk
from paddle_tpu.ops import select
from paddle_tpu.ops import selective_scan as ss


@pytest.fixture(autouse=True)
def _auto_kernel_mode():
    # the kernel mode is process-wide and servers built by earlier test
    # files may have left it pinned (GenerationServer(kernels=...)):
    # under "reference" PT_FLASH_INTERPRET asks for nothing
    prev = select.kernel_mode()
    select.set_kernel_mode("auto")
    yield
    select.set_kernel_mode(prev)


def _inputs(T, d, S, seed, rows=None):
    rng = np.random.default_rng(seed)
    lead = (T,) if rows is None else (rows,)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    x, Bm, Cm = f(*lead, d), f(*lead, S), f(*lead, S)
    dt = jnp.asarray(rng.uniform(1e-3, 0.2, lead + (d,)), jnp.float32)
    A = -jnp.exp(f(S, d) * 0.3)
    h = f(S, d) if rows is None else f(rows, S, d)
    return x, dt, A, Bm, Cm, f(d), h


def test_chunk_scan_reference_is_the_recurrence():
    x, dt, A, Bm, Cm, D, h0 = _inputs(5, 8, 4, 0)
    y, hT = ss.ssm_chunk_scan_ref(x, dt, A, Bm, Cm, D, h0)
    h = np.asarray(h0, np.float64)
    for t in range(5):
        h = (np.exp(np.asarray(dt[t])[None] * np.asarray(A)) * h
             + (np.asarray(dt[t]) * np.asarray(x[t]))[None]
             * np.asarray(Bm[t])[:, None])
        yt = (h * np.asarray(Cm[t])[:, None]).sum(0) \
            + np.asarray(D) * np.asarray(x[t])
        np.testing.assert_allclose(np.asarray(y[t]), yt, rtol=1e-5,
                                   atol=1e-6)
    np.testing.assert_allclose(np.asarray(hT), h, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("T,d", [(16, 256), (128, 1024)])
def test_chunk_scan_kernel_matches_its_composition(T, d, monkeypatch):
    monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
    args = _inputs(T, d, 16, 1)
    want = ss.ssm_chunk_scan_ref(*args)
    got = ss.ssm_chunk_scan_pallas(*args)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("rows,d", [(3, 256), (16, 2048)])
def test_step_kernel_matches_its_composition_and_keeps_masked_rows(
        rows, d, monkeypatch):
    monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
    x, dt, A, Bm, Cm, D, h = _inputs(1, d, 16, 2, rows=rows)
    dt = dt.at[1].set(0.0)                     # a masked row
    want = ss.ssm_step_ref(x, dt, A, Bm, Cm, D, h)
    got = ss.ssm_step_pallas(x, dt, A, Bm, Cm, D, h + 0.0)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-5,
                                   atol=1e-5)
    np.testing.assert_array_equal(np.asarray(got[1][1]), np.asarray(h[1]))


def test_the_selection_rule_names_what_it_picked():
    assert select.select_selective_scan((128, 16, 5120), platform="tpu",
                                        is_partitioned=False) == "pallas"
    assert select.select_selective_scan((1, 16, 5120), tokens=128,
                                        platform="tpu",
                                        is_partitioned=False) == "pallas"
    assert select.select_selective_scan((4, 16, 96), platform="tpu",
                                        is_partitioned=False) == "xla"
    assert select.select_selective_scan((4, 16, 5120), platform="cpu") == \
        "xla"
    # a 64-wide pool nobody packed stays on jnp, and so do 10 packed kv
    # heads in token-major blocks (no sublane multiple); head-major runs
    assert select.select_paged_attention(
        (128, 1, 40, 64), (64, 16, 20, 64), platform="tpu",
        is_partitioned=False) == "xla"
    assert select.select_paged_attention(
        (128, 1, 40, 128), (64, 16, 10, 128), platform="tpu",
        is_partitioned=False) == "xla"
    assert select.select_paged_attention(
        (128, 1, 40, 128), (64, 10, 16, 128), head_major=True,
        platform="tpu", is_partitioned=False) == "pallas"
    assert select.select_paged_attention(
        (64, 1, 32, 128), (64, 16, 8, 128), platform="tpu",
        is_partitioned=False) == "pallas"


@pytest.mark.parametrize("head_major", [False, True],
                         ids=["token-major", "head-major"])
@pytest.mark.parametrize("window,bs,ring", [(24, 8, 4), (512, 16, 33)])
def test_window_walk_matches_the_masked_ring(window, bs, ring, head_major,
                                             monkeypatch):
    """Rows at positions before, at and far past the window, read from a
    ring that has wrapped: the kernel's walk from the window's first block
    against the jnp mask over the whole ring, for both block layouts."""
    monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
    rng = np.random.default_rng(3)
    B, KV, rep, D = 5, 2, 4, 128
    pos = jnp.asarray([0, window - 3, window, 3 * window + 5,
                       7 * ring * bs + 1], jnp.int32)
    N = 1 + B * ring
    shape = (N, KV, bs, D) if head_major else (N, bs, KV, D)
    k_pool = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    tables = 1 + jnp.arange(B)[:, None] * ring + jnp.arange(ring)[None, :]
    q = jnp.asarray(rng.standard_normal((B, 1, KV * rep, D)), jnp.float32)
    got = pk.paged_attention(q, k_pool, v_pool, tables, pos, window=window,
                             ring=ring, head_major=head_major)
    monkeypatch.delenv("PT_FLASH_INTERPRET")
    want = pa.paged_window_attention(q, k_pool, v_pool, tables, pos, window,
                                     head_major=head_major)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("window,ring", [(24, 0), (0, 4)])
def test_window_and_ring_come_together(window, ring, monkeypatch):
    """The kernel walks a window through a ring table and nothing else: one
    without the other is refused by name, not walked some third way."""
    monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
    q = jnp.zeros((2, 1, 4, 128), jnp.float32)
    pool = jnp.zeros((9, 8, 2, 128), jnp.float32)
    tables = jnp.ones((2, 4), jnp.int32)
    with pytest.raises(ValueError, match="window and ring come together"):
        pk.paged_attention(q, pool, pool, tables, jnp.zeros((2,), jnp.int32),
                           window=window, ring=ring)


@pytest.mark.parametrize("B,W,KV,rep", [
    pytest.param(4, 1, 10, 4, id="decode-all-heads-10-kv-heads"),
    pytest.param(1, 24, 3, 4, id="chunk-per-head"),
    pytest.param(3, 3, 2, 2, id="verify-window"),
])
def test_head_major_pool_matches_the_token_major_one(B, W, KV, rep,
                                                     monkeypatch):
    """The same K/V written through the same tables into a head-major
    ``(N, KV, bs, D)`` pool and a token-major ``(N, bs, KV, D)`` one: the
    kernel (both bodies) and the jnp composition agree across layouts."""
    rng = np.random.default_rng(5)
    bs, M, D = 8, 6, 128
    N = 1 + B * M
    tables = jnp.asarray(1 + rng.permutation(B * M).reshape(B, M), jnp.int32)
    start = jnp.asarray(rng.integers(0, (M - 1) * bs - W, size=B), jnp.int32)
    ctx = jnp.asarray(rng.standard_normal((B, M * bs, KV, D)), jnp.float32)
    tok = jnp.zeros((N, bs, KV, D), jnp.float32).at[tables].set(
        ctx.reshape(B, M, bs, KV, D))
    head = jnp.zeros((N, KV, bs, D), jnp.float32)
    hk, hv = pa.write_window_kv(
        head, head, ctx, -ctx, tables, jnp.zeros((B,), jnp.int32),
        head_major=True)
    np.testing.assert_array_equal(np.asarray(hk.swapaxes(1, 2)),
                                  np.asarray(tok))
    q = jnp.asarray(rng.standard_normal((B, W, KV * rep, D)), jnp.float32)
    want = pa.paged_verify_attention(q, tok, -tok, tables, start)
    got_jnp = pa.paged_verify_attention(q, hk, hv, tables, start,
                                        head_major=True)
    np.testing.assert_allclose(np.asarray(got_jnp), np.asarray(want),
                               rtol=1e-6, atol=1e-6)
    monkeypatch.setenv("PT_FLASH_INTERPRET", "1")
    got = pk.paged_attention(q, hk, hv, tables, start, head_major=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_ring_positions_hold_the_newest_block_of_each_entry():
    pos = jnp.asarray([0, 17, 100])
    got = np.asarray(pa.ring_positions(4, 8, pos))
    for b, p in enumerate([0, 17, 100]):
        for r in range(4):
            js = [j for j in range(-8, p // 8 + 1) if j % 4 == r]
            assert got[b, r * 8] == max(js) * 8
