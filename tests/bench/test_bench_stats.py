"""The percentile and spread arithmetic every metric and bound uses."""
import statistics

import numpy as np
import pytest

from bench.stats import percentile, spread


@pytest.mark.parametrize("q", [0, 50, 90, 95, 99, 100])
def test_percentile_matches_numpy(q):
    x = np.random.default_rng(q).lognormal(size=1001)
    assert percentile(x, q) == pytest.approx(np.percentile(x, q), rel=1e-12)


def test_percentile_of_nothing_is_an_error():
    with pytest.raises(ValueError):
        percentile([], 95)


def test_percentile_keeps_a_failed_request_in_the_tail():
    assert percentile([1.0] * 99 + [float("inf")], 100) == float("inf")


def test_spread_is_the_interquartile_share_of_the_median():
    x = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    q1, med, q3 = statistics.quantiles(x, n=4)
    assert spread(x) == pytest.approx((q3 - q1) / med)
