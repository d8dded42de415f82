"""Window and percentile arithmetic of the benchmark (host side, no JAX).

Kept here, under ``benchmarks/``, so that no later PR can change how a tail
or a rate is taken. Percentiles are nearest-rank on the sorted sample (no
interpolation): a p95 is a value that was observed.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional, Sequence, Tuple


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100). ``inf`` entries
    (requests that never got there) sort last, so they are the tail."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if not 0 < q <= 100:
        raise ValueError(f"q must be in (0, 100], got {q}")
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[rank - 1]


def in_window(t: float, start: float, end: float) -> bool:
    """Half-open window [start, end)."""
    return start <= t < end


def rate_in_window(events: Sequence[Tuple[float, float]], start: float,
                   end: float) -> float:
    """Σ amount of the ``(time, amount)`` events that fall inside
    [start, end), over the window's WHOLE length — a stall inside the
    window lowers the rate, it is not cut out."""
    if end <= start:
        raise ValueError("empty window")
    total = sum(a for t, a in events if in_window(t, start, end))
    return total / (end - start)


def ttft_ms(due: float, first_token: Optional[float]) -> float:
    """Time to first token from the moment the request was DUE on the
    schedule (not from when the loop got round to submitting it). A request
    that never produced a token counts as the worst."""
    if first_token is None:
        return math.inf
    return (first_token - due) * 1e3


def tpot_ms(first_token: Optional[float], done: Optional[float],
            n_tokens: int) -> Optional[float]:
    """Mean gap between output tokens of one request, ms; None where the
    request has a single token (no gap exists); inf where it never ended."""
    if n_tokens < 2:
        return None
    if first_token is None or done is None:
        return math.inf
    return (done - first_token) / (n_tokens - 1) * 1e3


def finite_or_cap(x: float, cap: float) -> float:
    """JSON has no inf: a tail that is a request which never finished is
    reported as ``cap`` (the run's whole length in ms) — the worst the run
    could have observed."""
    return cap if math.isinf(x) else x


def backlog_slope(samples: Sequence[Tuple[float, float]]) -> float:
    """Least-squares slope (per second) of ``(time, backlog)`` samples:
    the sweep's test for a growing queue."""
    n = len(samples)
    if n < 2:
        return 0.0
    mt = sum(t for t, _ in samples) / n
    mb = sum(b for _, b in samples) / n
    den = sum((t - mt) ** 2 for t, _ in samples)
    if den == 0:
        return 0.0
    return sum((t - mt) * (b - mb) for t, b in samples) / den


def mean(xs: Sequence[float]) -> float:
    xs = list(xs)
    if not xs:
        raise ValueError("mean of an empty sample")
    return sum(xs) / len(xs)


def union_length(intervals: List[Tuple[float, float]]) -> float:
    """Total length covered by a set of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total
