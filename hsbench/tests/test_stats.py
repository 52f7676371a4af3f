import math

import pytest

from hsbench import stats


def test_percentile_interpolates_between_order_statistics():
    v = [10.0, 20.0, 30.0, 40.0, 50.0]
    assert stats.percentile(v, 0) == 10.0
    assert stats.percentile(v, 50) == 30.0
    assert stats.percentile(v, 100) == 50.0
    assert stats.percentile(v, 95) == pytest.approx(48.0)
    assert stats.percentile([7.0], 95) == 7.0
    assert math.isnan(stats.percentile([], 50))


def test_latency_counts_from_the_due_time_not_from_the_send():
    due = [0.0, 1.0, 2.0]
    sent = [0.0, 1.4, 2.0]   # the generator stalled before the second request
    done = [0.1, 1.5, None]  # the third failed: it has no latency
    assert stats.latencies_ms(due, done) == pytest.approx([100.0, 500.0])
    assert stats.lateness_ms(due, sent) == pytest.approx([0.0, 400.0, 0.0])


def test_spread_is_the_interquartile_distance_over_the_median():
    v = [100.0, 101.0, 102.0, 103.0, 104.0, 105.0]
    import statistics

    q = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q[2] - q[0]) / 102.5)
