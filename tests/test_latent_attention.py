"""Attention over a paged latent pool: the Pallas kernel (interpret mode on
the CPU) against the jnp composition, and the selection rules of the two new
ops (``ops/select.py``)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import latent_attention as la
from paddle_tpu.ops import latent_attention_pallas as lk
from paddle_tpu.ops import select


@pytest.fixture
def interpret():
    prev = select.kernel_mode()
    select.set_kernel_mode("pallas")
    yield
    select.set_kernel_mode(prev)


def _case(B, W, H, width, v_width, bs, M, pos, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    row = -(-width // 128) * 128
    N = 1 + B * M
    pool = np.zeros((N, bs, row), np.float32)
    pool[1:, :, :width] = rng.standard_normal((N - 1, bs, width))
    # a stale block full of NaNs that no live table entry names
    tables = 1 + np.arange(B * M).reshape(B, M)
    q = np.zeros((B, W, H, row), np.float32)
    q[..., :width] = rng.standard_normal((B, W, H, width)) * 0.3
    return (jnp.asarray(q, dtype), jnp.asarray(pool, dtype),
            jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32))


@pytest.mark.parametrize("name,B,W,H,pos", [
    ("decode-ragged-rows", 4, 1, 4, [0, 5, 37, 70]),
    ("chunk-of-16-at-32", 1, 16, 4, [32]),
    ("chunk-tiled-over-programs", 1, 128, 4, [0]),
])
def test_kernel_matches_the_composition(interpret, name, B, W, H, pos):
    """Positions cross the original length (16) so the query scale differs
    from row to row inside one call; entries past a row's frontier name
    blocks the kernel must not read (the composition masks them)."""
    q, pool, tables, posv = _case(B, W, H, 24, 16, 8, 20, pos)
    kw = dict(v_width=16, scale=0.31, qscale=(0.1, 16))
    want = la._reference(q, pool, tables, posv, 16, 0.31, (0.1, 16))
    got = lk.latent_attention(q, pool, tables, posv, **kw)
    assert got.shape == want.shape == (B, W, H, 16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)
    # through the seam, under the pinned mode: the same kernel
    select.selected(reset=True)
    out = la.latent_attention(q, pool, tables, posv, **kw)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(got))
    assert select.selected() == {
        "latent_attention": {"pallas-interpret": 1}}


@pytest.fixture
def groups_of_32(monkeypatch):
    """Groups of 4 blocks of 8 tokens, so that tables of 20 blocks hold up
    to 5 groups a row and the copy stream crosses rows mid-slot-parity."""
    monkeypatch.setattr(lk, "_GROUP_TOKENS", 32)


# every boundary of the one copy stream over a call's (row, tile, group)s
_STREAM_CASES = [
    ("one-block-beside-M-blocks", 4, 1, 4, [3, 159, 0, 150]),
    ("M-blocks-beside-one-block", 4, 1, 4, [159, 2, 158, 7]),
    ("rows-end-on-a-group-boundary", 4, 1, 4, [31, 63, 32, 127]),
    ("every-row-at-pos-0", 3, 1, 4, [0, 0, 0]),
    ("one-row-of-five-groups", 1, 1, 4, [159]),
    ("one-row-at-pos-0", 1, 1, 4, [0]),
    ("odd-then-even-group-counts", 5, 1, 4, [40, 100, 70, 20, 130]),
    ("long-first-and-last-row", 3, 1, 4, [140, 5, 155]),
    ("chunk-tiles-straddle-a-group", 1, 128, 4, [24]),
    ("chunk-tiles-end-on-a-group", 1, 128, 4, [32]),
    ("chunks-of-two-rows", 2, 128, 4, [8, 30]),
]


@pytest.mark.parametrize("name,B,W,H,pos", _STREAM_CASES)
def test_stream_crosses_rows_tiles_and_groups(interpret, groups_of_32, name,
                                              B, W, H, pos):
    q, pool, tables, posv = _case(B, W, H, 24, 16, 8, 20, pos, seed=3)
    want = la._reference(q, pool, tables, posv, 16, 0.31, (0.1, 16))
    got = lk.latent_attention(q, pool, tables, posv, v_width=16, scale=0.31,
                              qscale=(0.1, 16))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_blocks_past_the_frontier_are_never_read(interpret):
    q, pool, tables, posv = _case(2, 1, 4, 24, 16, 8, 6, [9, 3])
    pool = pool.at[0].set(jnp.nan)                 # the scratch block
    tables = tables.at[:, 2:].set(0)               # past both frontiers
    got = lk.latent_attention(q, pool, tables, posv, v_width=16, scale=0.3)
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("name,B,W,H,pos", _STREAM_CASES)
def test_the_row_ahead_reads_its_own_live_blocks_only(
        interpret, groups_of_32, name, B, W, H, pos):
    """A program fetches the NEXT program's first group: every block no live
    table entry names is poison, every dead entry names poison, and the
    output is what the clean pool gives, bit for bit."""
    q, pool, tables, posv = _case(B, W, H, 24, 16, 8, 20, pos, seed=4)
    kw = dict(v_width=16, scale=0.31, qscale=(0.1, 16))
    clean = lk.latent_attention(q, pool, tables, posv, **kw)
    tables, live = np.array(tables), np.zeros(pool.shape[0], bool)
    nlive = [(pos[b] + W - 1) // 8 + 1 for b in range(B)]
    for b in range(B):
        live[tables[b, :nlive[b]]] = True
    dead = np.flatnonzero(~live)                   # the scratch block too
    for b in range(B):
        tables[b, nlive[b]:] = dead[(7 * b + np.arange(20 - nlive[b]))
                                    % len(dead)]
        assert not live[tables[b, nlive[b]:]].any()
    pool = jnp.where(jnp.asarray(live)[:, None, None], pool, jnp.nan)
    got = lk.latent_attention(q, pool, jnp.asarray(tables), posv, **kw)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(clean))


def test_writes_land_where_the_tables_say():
    pool = jnp.zeros((6, 4, 128), jnp.float32)
    tables = jnp.asarray([[3, 5], [2, 0]], jnp.int32)
    rows = jnp.asarray(np.arange(2 * 128).reshape(2, 128), jnp.float32)
    out = la.write_latent_rows(pool, rows, tables, jnp.asarray([6, 1]))
    assert (np.asarray(out[5, 2]) == np.asarray(rows[0])).all()
    assert (np.asarray(out[2, 1]) == np.asarray(rows[1])).all()
    assert float(jnp.abs(out).sum()) == float(jnp.abs(rows).sum())
    chunk = jnp.asarray(np.random.default_rng(0).standard_normal((8, 128)),
                        jnp.float32)
    out = la.write_latent_chunk(pool, chunk, jnp.asarray([4, 1, 3, 0]),
                                jnp.int32(4))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(chunk[:4]))
    np.testing.assert_array_equal(np.asarray(out[3]), np.asarray(chunk[4:]))


@pytest.mark.parametrize("pool,v,want", [
    ((10001, 64, 384), 256, "pallas"),       # the serving cell's pool
    ((40001, 16, 384), 256, "pallas"),       # the same in blocks of 16
    ((64, 16, 320), 256, "xla"),             # a row that is no lane multiple
    ((64, 16, 128), 16, "xla"),              # a value part that is none
    ((64, 4, 384), 256, "xla"),              # a block of 4 tokens
])
def test_latent_attention_rule(pool, v, want):
    q = (128, 1, 32, pool[2])
    assert select.select_latent_attention(
        q, pool, v, platform="tpu", is_partitioned=False) == want
    assert select.select_latent_attention(
        q, pool, v, platform="tpu", is_partitioned=True) == "xla"
    assert select.select_latent_attention(q, pool, v, platform="cpu") == "xla"


def test_grouped_matmul_rule_and_the_two_products_agree():
    from paddle_tpu.ops.grouped_matmul import grouped_matmul

    x, w = (512, 4096), (32, 4096, 2048)
    on_tpu = select.select_grouped_matmul(x, w, platform="tpu",
                                          is_partitioned=False)
    assert on_tpu == select.GROUPED_MATMUL_ON_TPU
    assert select.select_grouped_matmul(x, w, platform="cpu") == "xla"
    assert select.select_grouped_matmul(
        x, w, platform="tpu", is_partitioned=True) == "xla"
    rng = np.random.default_rng(1)
    xs = jnp.asarray(rng.standard_normal((12, 16)), jnp.float32)
    ws = jnp.asarray(rng.standard_normal((3, 16, 8)), jnp.float32)
    sizes = jnp.asarray([5, 0, 4], jnp.int32)       # 3 rows in no group
    got = np.asarray(grouped_matmul(xs, ws, sizes))
    np.testing.assert_allclose(got[:5], np.asarray(xs[:5] @ ws[0]),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[5:9], np.asarray(xs[5:9] @ ws[2]),
                               rtol=1e-5, atol=1e-5)
