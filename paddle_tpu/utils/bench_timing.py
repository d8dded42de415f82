"""Device timing and the published peaks of the chips this repo measures on.

JAX dispatch is asynchronous: a wall-clock timing must close with something
that waits for the device. ``chip_smoke.py`` checks on every run that
``jax.block_until_ready`` does wait on the machine it runs on (N chained
steps closed by it take as long as the same steps closed by a scalar pull);
on the v5e machine it does — see CHANGES.md, PR 21.

The primitives here implement **dispatch-chain differencing**, which closes
with a device->host scalar pull and so holds either way: dispatch N calls
(they pipeline on-device), pull one scalar, and subtract the identically
shaped 1-call measurement so the fixed host round-trip cost cancels:

    device_time = [t(N+1 calls + pull) - t(1 call + pull)] / N

Requirement on ``fn``: repeated calls must serialize on-device — either
through a data dependency (train steps chained via donated params) or by
being independent launches on the same stream (the default for same-device
jitted calls).
"""
from __future__ import annotations

import time

__all__ = ["pull_scalar", "chain_seconds", "device_time_ms",
           "UnstableMeasurement", "DEVICE_PEAKS", "device_peaks",
           "peak_flops", "peak_hbm_bandwidth"]

#: Published per-chip peaks keyed by ``jax.devices()[0].device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" system architecture page
#: (197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip). A device that is
#: not listed is an error, not a default: add its row with its source.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class UnstableMeasurement(RuntimeError):
    """The differencing signal never cleared the observed noise floor.

    Distinct from generic RuntimeError so callers can skip-and-report
    without accidentally swallowing real device failures (XlaRuntimeError
    is also a RuntimeError subclass)."""


def device_peaks(device_kind: str | None = None) -> dict:
    """The :data:`DEVICE_PEAKS` row for ``device_kind`` (default: the
    first local device). Raises ``KeyError`` for an unlisted device — a
    utilization against a guessed peak is worse than none."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_PEAKS)} — add a row (with its source) to "
            f"paddle_tpu.utils.bench_timing.DEVICE_PEAKS") from None


def peak_flops(device_kind: str | None = None) -> float:
    """Peak bf16 FLOP/s per chip."""
    return device_peaks(device_kind)["bf16_flops"]


def peak_hbm_bandwidth(device_kind: str | None = None) -> float:
    """Peak HBM bytes/s per chip."""
    return device_peaks(device_kind)["hbm_bytes_per_s"]


def pull_scalar(out) -> float:
    """Force a real device->host sync by fetching one scalar of ``out``.

    Accepts any pytree of jax arrays or framework Tensors (anything whose
    leaves numpy can consume after ``jnp.asarray``).
    """
    import jax
    import jax.numpy as jnp

    leaves = [l for l in jax.tree_util.tree_leaves(out) if l is not None]
    if not leaves:
        raise ValueError(
            "pull_scalar: fn returned no array output to sync on (got an "
            "empty/None pytree) — the timing harness needs at least one "
            "device array to pull")
    leaf = leaves[0]
    value = getattr(leaf, "value", leaf)  # framework Tensor -> jax.Array
    return float(jnp.asarray(value).reshape(-1)[0].astype(jnp.float32))


def _chain_stats(fn, n: int, repeats: int) -> tuple[float, float]:
    """(min, max) wall time over ``repeats`` of: dispatch ``fn()`` ``n``
    times, then one scalar pull of the last output."""
    lo, hi = float("inf"), 0.0
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = None
        for _ in range(n):
            out = fn()
        pull_scalar(out)
        dt = time.perf_counter() - t0
        lo, hi = min(lo, dt), max(hi, dt)
    return lo, hi


def chain_seconds(fn, n: int, repeats: int = 3) -> float:
    """min-of-``repeats`` wall time of: dispatch ``fn()`` ``n`` times, then
    one scalar pull of the last output."""
    return _chain_stats(fn, n, repeats)[0]


def device_time_ms(fn, reps: int = 10, repeats: int = 3, warmup: int = 1,
                   min_signal_s: float | None = None,
                   max_reps: int = 1024) -> float:
    """Per-call device execution time of ``fn`` in milliseconds.

    Self-calibrating against the noise it actually observes: the required
    differencing signal is ``max(4 x measured spread, 10 ms)`` (or the
    explicit ``min_signal_s``), and reps double until the signal clears it.
    On a quiet backend sub-ms ops pass at small reps; on a jittery host
    the same code demands more signal — the adaptive floor is what keeps
    physically-impossible readings (observed at fixed small reps) out of
    benchmark tables.  ``UnstableMeasurement`` is raised
    at the reps cap rather than returning a sub-floor number.
    """
    out = None
    for _ in range(max(warmup, 1)):  # compile + steady-state
        out = fn()
    pull_scalar(out)
    while True:
        lo_long, hi_long = _chain_stats(fn, reps + 1, repeats)
        lo_short, hi_short = _chain_stats(fn, 1, repeats)
        diff = lo_long - lo_short
        spread = (hi_long - lo_long) + (hi_short - lo_short)
        floor = (min_signal_s if min_signal_s is not None
                 else max(4.0 * spread, 0.010))
        if diff >= floor:
            return diff / reps * 1e3
        if reps >= max_reps:
            raise UnstableMeasurement(
                f"{reps} reps stayed below the noise floor "
                f"(signal {diff * 1e3:.2f} ms < floor {floor * 1e3:.0f} ms, "
                f"spread {spread * 1e3:.0f} ms); the backend is too jittery "
                f"for this op")
        reps *= 2
