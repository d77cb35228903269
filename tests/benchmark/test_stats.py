"""The exact nearest-rank percentile the tails are taken with."""
import pytest

from benchmark.stats import nearest_rank


def test_p95_is_an_observed_sample():
    values = [float(v) for v in range(1, 101)]
    assert nearest_rank(values, 95) == 95.0
    assert nearest_rank(values, 50) == 50.0
    assert nearest_rank(list(reversed(values)), 95) == 95.0


def test_p95_small_samples():
    # 20 samples: rank ceil(0.95 * 20) = 19, not an interpolation
    values = [10.0 * i for i in range(20)]
    assert nearest_rank(values, 95) == 180.0
    assert nearest_rank([7.0], 95) == 7.0
    with pytest.raises(ValueError):
        nearest_rank([], 95)
