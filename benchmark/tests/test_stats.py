"""Percentile and spread arithmetic on synthetic samples."""

import statistics

import pytest

from benchmark.harness import stats


def test_percentile_matches_linear_interpolation():
    xs = list(range(1, 101))            # 1..100
    assert stats.percentile(xs, 0.5) == 50.5
    assert stats.percentile(xs, 0.95) == pytest.approx(95.05)
    assert stats.percentile(xs, 0.0) == 1
    assert stats.percentile(xs, 1.0) == 100
    assert stats.percentile([7.0], 0.95) == 7.0


def test_percentile_is_order_free_and_refuses_nothing():
    assert stats.percentile([3, 1, 2], 0.5) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)
    with pytest.raises(ValueError):
        stats.percentile([1, 2], 1.5)


@pytest.mark.parametrize("n,want", [(15, 0.5), (100, 0.9), (200, 0.95),
                                    (999, 0.95), (1000, 0.99)])
def test_supported_tail_leaves_ten_beyond(n, want):
    assert stats.supported_tail(n) == want


def test_iqr_spread_is_the_contracts():
    values = [100, 101, 99, 103, 97, 100]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.iqr_spread(values) == (q3 - q1) / statistics.median(values)
