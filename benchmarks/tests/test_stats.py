"""Percentile arithmetic and the failed-counts-as-beyond rule."""

import pytest

from benchmarks import stats


def test_nearest_rank():
    v = [float(i) for i in range(1, 101)]
    assert stats.percentile(v, 50) == 50.0
    assert stats.percentile(v, 95) == 95.0
    assert stats.percentile(v, 99) == 99.0
    assert stats.percentile(v, 100) == 100.0
    assert stats.percentile([3.0], 99) == 3.0


def test_failed_count_as_beyond():
    v = [float(i) for i in range(1, 96)]  # 95 measured, 5 failed
    assert stats.percentile_with_failed(v, 5, 95, 10_000.0) == 95.0
    assert stats.percentile_with_failed(v, 5, 96, 10_000.0) == 10_000.0
    assert stats.percentile_with_failed(v, 5, 50, 10_000.0) == 50.0
    # one failure in a hundred moves the p99 to the deadline, not the p95
    v = [1.0] * 99
    assert stats.percentile_with_failed(v, 1, 99, 10_000.0) == 1.0
    assert stats.percentile_with_failed(v, 2, 99, 10_000.0) == 10_000.0


def test_no_samples_is_an_error():
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    with pytest.raises(ValueError):
        stats.percentile_with_failed([], 0, 50, 1.0)


def test_samples_beyond():
    assert stats.samples_beyond(1000, 99) == 10
    assert stats.samples_beyond(7200, 95) == 360
