"""Unit tests for paddle_tpu.utils.bench_timing — the dispatch-chain
differencing harness every benchmark tool times through.

The failure modes this module exists for (a sync that does not wait,
seconds-scale jitter) are simulated with fakes; the real-backend behavior is
exercised by the benchmark tools themselves on hardware.
"""
import time

import jax.numpy as jnp
import pytest

from paddle_tpu.utils import bench_timing as bt


def test_pull_scalar_jax_array_and_tensor():
    import paddle_tpu as paddle

    assert bt.pull_scalar(jnp.arange(4.0)) == 0.0
    assert bt.pull_scalar(paddle.to_tensor([3.0, 1.0])) == 3.0
    # pytrees: first non-None leaf wins
    assert bt.pull_scalar({"a": None, "b": jnp.full((2, 2), 7.0)}) == 7.0


def test_device_time_ms_measures_a_known_busy_wait():
    target_s = 0.004

    def fn():
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < target_s:
            pass
        return jnp.zeros(())

    ms = bt.device_time_ms(fn, reps=8, repeats=2)
    # busy-wait is the per-call cost; allow generous slack for CI hosts
    assert 0.5 * target_s * 1e3 <= ms <= 3.0 * target_s * 1e3


def test_device_time_ms_raises_unstable_on_pure_jitter(monkeypatch):
    # a "backend" where every chain takes the same time regardless of n
    # (zero signal) but with spread: must raise, never return ~0
    calls = iter([0.5, 0.9] * 50)

    def fake_chain(fn, n, repeats):
        a, b = next(calls), next(calls)
        return min(a, b), max(a, b)

    monkeypatch.setattr(bt, "_chain_stats", fake_chain)
    with pytest.raises(bt.UnstableMeasurement):
        bt.device_time_ms(lambda: jnp.zeros(()), reps=4, max_reps=16)


def test_unstable_is_not_a_generic_runtime_error_catchall():
    # callers catch UnstableMeasurement specifically; a raw RuntimeError
    # (e.g. an XLA OOM) must NOT be an instance of it
    assert issubclass(bt.UnstableMeasurement, RuntimeError)
    assert not isinstance(RuntimeError("boom"), bt.UnstableMeasurement)


def test_adaptive_floor_scales_with_observed_spread(monkeypatch):
    # quiet backend: tiny spread -> small reps suffice even for a fast fn
    def fake_chain(fn, n, repeats):
        base = 0.001 * n + 0.050  # 1 ms/call + 50 ms fixed cost, no jitter
        return base, base + 0.0001

    monkeypatch.setattr(bt, "_chain_stats", fake_chain)
    ms = bt.device_time_ms(lambda: jnp.zeros(()), reps=16)
    assert ms == pytest.approx(1.0, rel=0.05)


def test_peaks_are_keyed_by_device_kind():
    # the v5e row, as published (Google Cloud "TPU v5e" page)
    assert bt.peak_flops("TPU v5 lite") == 197e12
    assert bt.peak_hbm_bandwidth("TPU v5 lite") == 819e9
    assert bt.device_peaks("TPU v5 lite")["hbm_bytes"] == 16e9


def test_peaks_default_to_the_local_device_and_never_guess():
    # the CPU test host is not in the table: asking for "the local chip's
    # peak" must raise, not fall back to some TPU generation
    with pytest.raises(KeyError, match="device_kind"):
        bt.peak_flops()
