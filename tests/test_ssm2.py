"""ops/ssm2.py (Mamba-2's state update): the compositions against the
recurrence written out by hand, the chunked (dual) form against the
sequential recurrence, and the selection rules."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import select
from paddle_tpu.ops import ssm2


@pytest.fixture(autouse=True)
def _auto_kernel_mode():
    # process-wide, and earlier test files may have left it pinned
    prev = select.kernel_mode()
    select.set_kernel_mode("auto")
    yield
    select.set_kernel_mode(prev)


def _inputs(lead, H, P, N, seed, fast=4.0):
    """Operands of ``lead`` rows (a step) or tokens (a chunk): decays from
    all but 1 to all but 0 within the heads."""
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    x, Bm, Cm = f(lead, H, P), f(lead, N), f(lead, N)
    dt = jnp.asarray(rng.uniform(1e-3, 0.3, (lead, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, fast, (H,)), jnp.float32)
    return x, dt, A, Bm, Cm, f(H)


def _by_hand(x, dt, A, Bm, Cm, D, h):
    """The recurrence in float64, a token at a time: x (T, H, P), h (H, P,
    N)."""
    x, dt, A, Bm, Cm, D = (np.asarray(a, np.float64)
                           for a in (x, dt, A, Bm, Cm, D))
    h = np.asarray(h, np.float64)
    ys = []
    for t in range(x.shape[0]):
        h = (np.exp(dt[t] * A)[:, None, None] * h
             + (dt[t][:, None] * x[t])[:, :, None] * Bm[t][None, None, :])
        ys.append((h * Cm[t][None, None, :]).sum(-1) + D[:, None] * x[t])
    return np.stack(ys), h


def test_the_references_are_the_recurrence():
    H, P, N = 3, 4, 8
    x, dt, A, Bm, Cm, D = _inputs(6, H, P, N, 0)
    h0 = jnp.asarray(np.random.default_rng(1).standard_normal((H, P, N)),
                     jnp.float32)
    want_y, want_h = _by_hand(x, dt, A, Bm, Cm, D, h0)
    y, hT = ssm2.ssd_chunk_ref(x, dt, A, Bm, Cm, D, h0)
    np.testing.assert_allclose(np.asarray(y), want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), want_h, rtol=1e-5, atol=1e-5)
    # one step over 6 ROWS = 6 one-token chunks, each from its own state
    hs = jnp.stack([h0 * (i + 1) for i in range(6)])
    y, h1 = ssm2.ssm2_step_ref(x, dt, A, Bm, Cm, D, hs)
    for b in range(6):
        wy, wh = _by_hand(x[b:b + 1], dt[b:b + 1], A, Bm[b:b + 1],
                          Cm[b:b + 1], D, hs[b])
        np.testing.assert_allclose(np.asarray(y[b]), wy[0], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(h1[b]), wh, rtol=1e-5,
                                   atol=1e-5)


@pytest.mark.parametrize("T,n_valid,chunk", [
    pytest.param(32, 21, 32, id="one-block-ragged"),
    pytest.param(32, 32, 32, id="one-block-full"),
    pytest.param(64, 37, 16, id="four-blocks-the-valid-end-inside-the-third"),
    pytest.param(8, 1, 256, id="shorter-than-a-block-one-valid-token"),
])
def test_chunked_form_is_the_sequential_recurrence_from_a_nonzero_state(
        T, n_valid, chunk):
    """From a NON-zero state, with ``n_valid`` < the chunk (``dt`` = 0 past
    it, as the caller masks): the outputs of the valid tokens and the state
    after the last valid one."""
    H, P, N = 4, 8, 16
    x, dt, A, Bm, Cm, D = _inputs(T, H, P, N, 2, fast=16.0)
    h0 = jnp.asarray(np.random.default_rng(3).standard_normal((H, P, N)),
                     jnp.float32)
    dt = jnp.where(jnp.arange(T)[:, None] < n_valid, dt, 0.0)
    want_y, want_h = ssm2.ssd_chunk_ref(x, dt, A, Bm, Cm, D, h0)
    by_hand = _by_hand(x[:n_valid], dt[:n_valid], A, Bm[:n_valid],
                       Cm[:n_valid], D, h0)[1]
    np.testing.assert_allclose(np.asarray(want_h), by_hand, rtol=1e-5,
                               atol=1e-5)
    y, hT = ssm2.ssd_chunk(x, dt, A, Bm, Cm, D, h0, chunk)
    np.testing.assert_allclose(np.asarray(y[:n_valid]),
                               np.asarray(want_y[:n_valid]), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(want_h), rtol=1e-5,
                               atol=1e-5)
    assert np.isfinite(np.asarray(y)).all()
    with pytest.raises(ValueError, match="no multiple"):
        ssm2.ssd_chunk_dual(x, dt, A, Bm, Cm, D, h0, chunk=T - 1)


def test_a_fast_head_does_not_overflow_the_chunked_form():
    """A head that forgets within a token (dt A = -40 a token: the masked
    exponent above the diagonal would be +10,000 over a block)."""
    H, P, N, T = 2, 8, 16, 256
    x, dt, _, Bm, Cm, D = _inputs(T, H, P, N, 4)
    A = jnp.asarray([-400.0, -1.0], jnp.float32)
    dt = jnp.full((T, H), 0.1, jnp.float32)
    h0 = jnp.ones((H, P, N), jnp.float32)
    want_y, want_h = ssm2.ssd_chunk_ref(x, dt, A, Bm, Cm, D, h0)
    y, hT = ssm2.ssd_chunk(x, dt, A, Bm, Cm, D, h0)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), rtol=1e-4,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(want_h), rtol=1e-4,
                               atol=1e-5)


def test_rows_with_dt_zero_keep_their_state_bit_for_bit():
    """How the caller masks idle and prefilling slots."""
    B, H, P, N = 4, 8, 16, 128
    x, dt, A, Bm, Cm, D = _inputs(B, H, P, N, 7)
    h = jnp.asarray(np.random.default_rng(8).standard_normal((B, H, P, N))
                    * 1e3, jnp.float32)
    dt = dt.at[1].set(0.0).at[3].set(0.0)
    select.selected(reset=True)
    _, h1 = ssm2.ssm2_step(x, dt, A, Bm, Cm, D, h)
    assert select.selected()["ssm2_step"] == {"xla": 1}
    for b in (1, 3):
        np.testing.assert_array_equal(np.asarray(h1[b]), np.asarray(h[b]))
    assert not np.array_equal(np.asarray(h1[0]), np.asarray(h[0]))


def test_selection_rules():
    cell = (96, 128, 64, 128)
    # both by measurement, whatever the platform (ops/select.py says why)
    for platform in ("tpu", "cpu"):
        assert select.select_ssm2_step(cell, platform=platform) == "xla"
        assert select.select_ssd_chunk(cell[1:], 256,
                                       platform=platform) == "xla"
    # the grouped product takes rows that fill no whole tile (padded up)
    w = (36, 4096, 768)
    for rows in (960, 2560):
        assert select.select_grouped_matmul(
            (rows, 4096), w, platform="tpu",
            is_partitioned=False) == select.GROUPED_MATMUL_ON_TPU
    assert select.select_grouped_matmul(
        (72, 4096), w, platform="tpu", is_partitioned=False) == "xla"
