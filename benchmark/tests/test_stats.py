import pytest

from harness import stats


def test_percentiles_on_known_samples():
    s = list(range(1, 101))  # 1..100
    assert stats.median(s) == 50.5
    assert stats.percentile(s, 95.0) == pytest.approx(95.05)
    assert stats.percentile([7.0], 95.0) == 7.0
    assert stats.percentile([1.0, 3.0], 50.0) == 2.0
    import numpy as np

    rng = np.random.default_rng(3)
    v = rng.exponential(1.0, 1234).tolist()
    for p in (50.0, 90.0, 95.0, 99.0):
        assert stats.percentile(v, p) == pytest.approx(float(np.percentile(v, p)))
    with pytest.raises(ValueError):
        stats.percentile([], 50.0)


@pytest.mark.parametrize("n,want", [(50, 50.0), (100, 90.0), (200, 95.0), (999, 95.0),
                                    (1000, 99.0), (10000, 99.9)])
def test_highest_percentile_with_ten_beyond(n, want):
    assert stats.highest_percentile(n) == want


def test_spread_is_the_contracts():
    import statistics

    v = [10.0, 10.2, 9.9, 10.4, 10.1, 9.8]
    q1, q2, q3 = statistics.quantiles(v, n=4)
    assert stats.iqr_share(v) == pytest.approx((q3 - q1) / q2)
