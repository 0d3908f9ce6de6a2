"""Percentile and rate arithmetic on fixed samples."""
import numpy as np
import pytest

from chipbench import stats


def test_percentile_interpolates_like_numpy():
    xs = [0.9, 0.1, 0.5, 0.3, 0.7, 2.0, 0.2]
    for q in (0, 5, 50, 95, 99, 100):
        assert stats.percentile(xs, q) == pytest.approx(np.percentile(xs, q))
    assert stats.percentile([1.0, 2.0, 3.0, 4.0], 50) == 2.5
    assert stats.percentile(list(range(101)), 95) == 95
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_rate_is_work_over_window():
    assert stats.rate(90, 45.0) == 2.0
    with pytest.raises(ValueError):
        stats.rate(1, 0.0)
