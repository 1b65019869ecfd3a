import math

import pytest

from benchmarks.harness import stats


def test_percentile_interpolates_like_numpy():
    import numpy as np

    vals = [5.0, 1.0, 9.0, 3.0, 7.0, 2.0]
    for q in (0, 10, 50, 90, 99, 100):
        assert stats.percentile(vals, q) == pytest.approx(float(np.percentile(vals, q)))


def test_failed_requests_miss_every_percentile_they_reach():
    vals = stats.with_failures([10.0] * 8 + [None, None])
    assert stats.percentile(vals, 50) == 10.0
    assert stats.percentile(vals, 90) == math.inf  # 2 of 10 failed


def test_samples_beyond_and_empty():
    assert stats.samples_beyond(101, 90) == 10
    assert stats.samples_beyond(100, 90) == 9
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_tokens_in_window_and_union():
    assert stats.tokens_in_window([-1.0, 0.0, 2.0, 5.0], [1, 1, 2, 4], 0.0, 5.0) == 3
    assert stats.union_length([(0, 2), (1, 3), (5, 6)]) == 4.0
    assert stats.union_length([]) == 0.0


def test_iqr_share_is_the_contracts_spread():
    import statistics

    vals = [100, 101, 99, 102, 98, 100.5]
    q1, med, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == pytest.approx((q3 - q1) / med)


def test_in_flight_thirds():
    recs = [{"t_send": 0.0, "t_end": 9.0}, {"t_send": 3.0, "t_end": None}, {"t_send": None}]
    assert stats.in_flight_thirds(recs, 9.0) == [1.0, 2.0, 2.0]
