"""Exact percentiles over raw latencies."""
import statistics

import numpy as np
import pytest

from bench.harness.stats import percentile


@pytest.mark.parametrize("q", [0, 5, 25, 50, 75, 95, 99, 100])
def test_percentile_matches_linear_interpolation(q):
    rng = np.random.default_rng(7)
    xs = list(rng.lognormal(0.0, 1.0, size=1001))
    assert percentile(xs, q) == pytest.approx(
        float(np.percentile(xs, q, method="linear")), rel=1e-12)


def test_percentile_of_known_samples_is_exact():
    # 20 samples 1..20: the 95th percentile lies 0.05 of the way from 19
    # to 20, the median halfway between 10 and 11
    xs = [float(i) for i in range(20, 0, -1)]
    assert percentile(xs, 95) == pytest.approx(19.05)
    assert percentile(xs, 50) == 10.5
    assert percentile([3.0], 95) == 3.0


def test_percentile_is_over_all_samples_not_a_median_of_pieces():
    # one slow half and one fast half: the p95 of the whole is in the slow
    # half, while the median of the two halves' p95s is not
    fast = [1.0] * 100
    slow = [1.0] * 80 + [50.0] * 20
    whole = percentile(fast + slow, 95)
    pieces = statistics.median([percentile(fast, 95), percentile(slow, 95)])
    assert whole == 50.0
    assert pieces < whole


def test_percentile_rejects_empty_and_out_of_range():
    with pytest.raises(ValueError):
        percentile([], 50)
    with pytest.raises(ValueError):
        percentile([1.0], 101)

