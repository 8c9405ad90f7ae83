"""Percentile, window and spread arithmetic on known samples."""

import statistics

import pytest

from chipbench import stats


def test_percentile_known_samples():
    data = [10, 20, 30, 40, 50]
    assert stats.percentile(data, 0) == 10
    assert stats.percentile(data, 50) == 30
    assert stats.percentile(data, 100) == 50
    assert stats.percentile(data, 25) == 20
    assert stats.percentile(data, 95) == pytest.approx(48.0)
    # order does not matter, and one sample is its own every percentile
    assert stats.percentile([50, 10, 40, 20, 30], 95) == pytest.approx(48.0)
    assert stats.percentile([7.5], 95) == 7.5


def test_percentile_matches_numpy_rule():
    np = pytest.importorskip("numpy")
    data = [0.3, 9.1, 4.4, 4.4, 2.0, 7.7, 1.1, 8.8, 5.5, 6.6, 0.1]
    for q in (1, 5, 50, 90, 95, 99):
        assert stats.percentile(data, q) == pytest.approx(
            float(np.percentile(data, q)))


def test_percentile_refuses_nothing_and_nonsense():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile([1, 2], 101)


def test_window_is_half_open_and_rate_is_over_the_whole_window():
    stamps = [0.9, 1.0, 1.5, 2.999, 3.0, 4.0]
    assert stats.in_window(stamps, 1.0, 3.0) == 3  # 1.0 in, 3.0 out
    # every event of the window over ALL of its length, lull included
    assert stats.rate_in_window(stamps, 1.0, 3.0) == pytest.approx(1.5)
    assert stats.rate_in_window([], 0.0, 10.0) == 0.0
    with pytest.raises(ValueError):
        stats.rate_in_window(stamps, 2.0, 2.0)


def test_samples_beyond():
    assert stats.samples_beyond(400, 95) == 20
    assert stats.samples_beyond(150, 95) == 7
    assert stats.samples_beyond(10, 50) == 5


def test_iqr_spread_is_the_drivers_rule():
    values = [100.0, 101.0, 99.0, 102.0, 98.0, 100.5]
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.iqr_spread(values) == pytest.approx(
        (q3 - q1) / statistics.median(values))
    with pytest.raises(ValueError):
        stats.iqr_spread([-1.0, 0.0, 0.0, 1.0])
