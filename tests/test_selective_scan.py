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


# rows of a window of 64 over rings of 9 blocks of 8, walked 4 blocks a
# group: (position, table) — "slot" is the slot's own consecutive ring
_RING_ROWS = {
    "below-the-window": (37, "slot"),       # one whole group, one partial
    "whole-groups": (207, "slot"),          # two whole groups, no wrap
    "crosses-the-ring-end": (276, "slot"),  # wraps, then whole, then partial
    "masked": (100, "zeros"),               # a masked row: all scratch
    "permuted": (207, "reversed"),          # not slot-affine: block by block
}


@pytest.mark.parametrize("row", list(_RING_ROWS))
def test_ring_walk_one_copy_is_the_per_block_walk(row, monkeypatch):
    """The window walk over 16 KB head-major blocks (4 KV heads of 128,
    float32) takes whole-group copies: against the jnp mask over the ring,
    and BITWISE against the same walk made block by block, for a row whose
    walk crosses the ring's end, a partial last group, a row before the
    window, a masked row and a ring table that is not the slot's run."""
    from paddle_tpu.autotune.kernel_geometry import PagedAttentionGeometry

    rng = np.random.default_rng(7)
    B, KV, rep, D, bs, window, ring = 3, 4, 2, 128, 8, 64, 9
    assert pk.whole_group_copies(KV, bs, D, 4, ring, True)
    geom = PagedAttentionGeometry(kv_block_depth=4)
    p, kind = _RING_ROWS[row]
    # the row under test is slot 1, between two rows at other positions
    pos = jnp.asarray([276, p, 37], jnp.int32)
    tables = np.asarray(1 + np.arange(B)[:, None] * ring
                        + np.arange(ring)[None, :], np.int32)
    if kind == "zeros":
        tables[1] = 0
    elif kind == "reversed":
        tables[1] = tables[1][::-1]
    tables = jnp.asarray(tables)
    shape = (1 + B * ring, KV, bs, D)
    k_pool = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, 1, KV * rep, D)), jnp.float32)
    monkeypatch.setenv("PT_FLASH_INTERPRET", "1")

    def walk():
        return np.asarray(pk.paged_attention(
            q, k_pool, v_pool, tables, pos, geometry=geom, window=window,
            ring=ring, head_major=True))

    got = walk()
    monkeypatch.setattr(pk, "_GROUP_COPY_MAX_BLOCK_BYTES", 0)
    assert not pk.whole_group_copies(KV, bs, D, 4, ring, True)
    np.testing.assert_array_equal(got, walk())
    monkeypatch.delenv("PT_FLASH_INTERPRET")
    want = pa.paged_window_attention(q, k_pool, v_pool, tables, pos, window,
                                     head_major=True)
    np.testing.assert_allclose(got, np.asarray(want), rtol=2e-5, atol=2e-5)


def _ring_walk_groups_by_hand(pos, window, ring, bs, G):
    """The groups of each walk, listed: a group fits one copy when it holds
    G blocks whose ring entries run up without passing the ring's end."""
    groups = fits = 0
    for p in pos:
        blocks = list(range(max(p - window + 1, 0) // bs, p // bs + 1))
        for i in range(0, len(blocks), G):
            entries = [j % ring for j in blocks[i:i + G]]
            groups += 1
            fits += len(entries) == G and entries == list(
                range(entries[0], entries[0] + G))
    return groups, fits


@pytest.mark.parametrize("window,bs,ring,G", [(2048, 16, 129, 16),
                                              (512, 16, 33, 16),
                                              (64, 8, 9, 4), (24, 8, 4, 4)])
def test_ring_walk_groups_are_the_walks_listed(window, bs, ring, G):
    pos = list(range(0, 12 * window, 7))
    assert pk.ring_walk_groups(np.asarray(pos), window, ring, bs, G) == \
        _ring_walk_groups_by_hand(pos, window, ring, bs, G)
    assert pk.ring_walk_groups(np.zeros((0,), np.int64), window, ring, bs,
                               G) == (0, 0)


# (KV, bs, D, itemsize, ring, head_major) of each paged attention call of
# the benchmark's cells (benchmarks/configs): bf16 pools, 16-token blocks
# (a chunk of a full layer is the same call as its walk, with more rows)
_CELL_WALKS = {
    "trinity-window": ((4, 16, 128, 2, 129, True), True),
    "trinity-full-walk-and-chunk": ((4, 16, 128, 2, 0, True), False),
    "phi-window": ((10, 16, 128, 2, 33, True), False),
    "phi-full-cross-and-chunk": ((10, 16, 128, 2, 0, True), False),
    "mistral7b-walk-and-chunk": ((8, 16, 128, 2, 0, False), False),
    "granite-attention": ((8, 16, 128, 2, 0, False), False),
}


@pytest.mark.parametrize("call", list(_CELL_WALKS))
def test_only_trinitys_window_walk_takes_whole_group_copies(call):
    """The rule is a property of the shapes: at the cells' shapes only the
    ring walk of 16 KB blocks (Trinity-Mini's window layers) takes it;
    Phi's 40 KB ring blocks and every walk without a ring keep the
    per-block walk (Mistral-Small-4 runs no paged kernel)."""
    shapes, want = _CELL_WALKS[call]
    assert pk.whole_group_copies(*shapes) is want


@pytest.mark.parametrize("cell,window,ring,lo,hi", [
    ("trinity", 2048, 129, 0.75, 0.85), ("phi", 512, 33, 0.0, 0.0)])
def test_the_group_share_of_ring_walks_at_the_cells_shapes(cell, window,
                                                           ring, lo, hi):
    """``serving_ring_groups``' share of whole-group copies at the cells'
    contexts (4-12 k tokens): ~0.8 in Trinity-Mini's window layers (8-9
    groups a walk, one crossing the ring's end in ~15 walks of 16, a
    partial last one in about every other), 0 in Phi's, where the rule
    says no."""
    KV = {"trinity": 4, "phi": 10}[cell]
    G, _ = pk.group_plan(32 // 4 if cell == "trinity" else 4, KV, 16, 128,
                         ring, 2)
    assert G == 16
    pos = np.random.default_rng(0).integers(4000, 12000, size=20000)
    groups, fits = pk.ring_walk_groups(pos, window, ring, 16, G)
    one_copy = pk.whole_group_copies(KV, 16, 128, 2, ring, True)
    share = (fits if one_copy else 0) / groups
    assert lo <= share <= hi


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
