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


def test_blocks_past_the_frontier_are_never_read(interpret):
    q, pool, tables, posv = _case(2, 1, 4, 24, 16, 8, 6, [9, 3])
    pool = pool.at[0].set(jnp.nan)                 # the scratch block
    tables = tables.at[:, 2:].set(0)               # past both frontiers
    got = lk.latent_attention(q, pool, tables, posv, v_width=16, scale=0.3)
    assert np.isfinite(np.asarray(got)).all()


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
