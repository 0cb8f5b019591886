"""Percentiles over all requests, and the seeded open-loop schedule."""
import numpy as np
import pytest

from bench.drivers.stream import arrivals
from bench.stats import nearest_rank


def test_nearest_rank_is_a_value_some_request_saw():
    lat = list(range(1, 101))                  # 1..100
    assert nearest_rank(lat, 0.50) == 50
    assert nearest_rank(lat, 0.95) == 95
    assert nearest_rank(lat, 0.99) == 99
    assert nearest_rank(lat, 1.0) == 100
    assert nearest_rank([7.5], 0.99) == 7.5
    # order does not matter; every request counts
    assert nearest_rank(lat[::-1], 0.95) == 95
    assert nearest_rank([1, 2, 3, 1000], 0.5) == 2


def test_nearest_rank_rejects_empty_and_bad_quantiles():
    with pytest.raises(ValueError):
        nearest_rank([], 0.5)
    with pytest.raises(ValueError):
        nearest_rank([1.0], 0.0)


def test_schedule_is_deterministic_for_a_seed():
    a = arrivals(640.0, 10.0, 2 ** 31 + 11)
    b = arrivals(640.0, 10.0, 2 ** 31 + 11)
    assert np.array_equal(a, b)
    assert len(a) == 6400
    assert np.all(np.diff(a) > 0)
    assert a[-1] == pytest.approx(10.0)


def test_seeds_reorder_the_same_gaps():
    a = arrivals(640.0, 10.0, 1)
    b = arrivals(640.0, 10.0, 2 ** 33 + 1)     # high bits count too
    assert not np.array_equal(a, b)
    ga = np.sort(np.diff(np.concatenate([[0.0], a])))
    gb = np.sort(np.diff(np.concatenate([[0.0], b])))
    assert np.allclose(ga, gb)
    # exponential gaps: the mean is 1 / rate, the median ln 2 / rate
    assert ga.mean() == pytest.approx(1 / 640.0)
    assert np.median(ga) == pytest.approx(np.log(2) / 640.0, rel=0.05)
