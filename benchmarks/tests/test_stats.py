import math

import pytest

from benchmarks import stats


def test_percentile_is_nearest_rank_and_observed():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def test_unfinished_requests_are_the_tail():
    xs = [10.0] * 18 + [math.inf, math.inf]
    assert math.isinf(stats.percentile(xs, 95))
    assert stats.finite_or_cap(stats.percentile(xs, 95), 60000.0) == 60000.0
    assert stats.percentile(xs, 90) == 10.0


def test_rate_counts_the_whole_window_stall_included():
    # 100 tokens/s for 4 s, a 4 s stall, 100 tokens/s for 2 s: the rate is
    # over all 10 s, not over the busy 6
    ev = [(t / 10, 10) for t in range(1, 41)] + \
         [(8 + t / 10, 10) for t in range(1, 21)]
    assert stats.rate_in_window(ev, 0.0, 10.0) == pytest.approx(59.0)
    # half-open: an event at the window's end is outside it
    assert stats.rate_in_window([(10.0, 5)], 0.0, 10.0) == 0.0
    assert stats.rate_in_window([(0.0, 5)], 0.0, 10.0) == 0.5


def test_ttft_is_from_due_time_and_tpot_needs_two_tokens():
    assert stats.ttft_ms(1.0, 1.25) == pytest.approx(250.0)
    assert math.isinf(stats.ttft_ms(1.0, None))
    assert stats.tpot_ms(1.0, 2.0, 11) == pytest.approx(100.0)
    assert stats.tpot_ms(1.0, 2.0, 1) is None
    assert math.isinf(stats.tpot_ms(1.0, None, 5))


def test_backlog_slope_and_union():
    assert stats.backlog_slope([(t, 2.0 * t + 1) for t in range(10)]) == \
        pytest.approx(2.0)
    assert stats.backlog_slope([(0, 3)]) == 0.0
    assert stats.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == \
        pytest.approx(4.0)
    assert stats.union_length([]) == 0.0
