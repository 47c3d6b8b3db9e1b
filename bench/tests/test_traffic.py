"""Arrival gaps of the served traffic: every seed offers the same gaps in
another order, at the file's mean rate, exponentially spread."""
import numpy as np
import pytest

from bench.lib import traffic as TR


@pytest.mark.parametrize("rate_hz,n", [(4.8, 240), (6.0, 300)])
def test_poisson_gaps_are_stratified(rate_hz, n):
    a = TR.stratified_poisson_gaps(rate_hz, n, TR.rng_for(2**40 + 7, 7))
    b = TR.stratified_poisson_gaps(rate_hz, n, TR.rng_for(12, 7))
    assert not np.array_equal(a, b)
    np.testing.assert_array_equal(np.sort(a), np.sort(b))
    assert abs(a.mean() * rate_hz - 1.0) < 0.02
    assert abs(a.std() / a.mean() - 1.0) < 0.15


def test_lengths_and_budgets_are_stratified():
    n = 240
    spec = {"below_share": 0.1875, "below_value": 0.5, "uniform": [1.0, 8.5]}
    la = TR.stratified_lognormal_ints(n, 32, 0.7, 4, 128, TR.rng_for(3, 7))
    lb = TR.stratified_lognormal_ints(n, 32, 0.7, 4, 128, TR.rng_for(4, 7))
    np.testing.assert_array_equal(np.sort(la), np.sort(lb))
    assert la.min() >= 4 and la.max() <= 128 and np.median(la) == 32
    ba, bb = TR.budgets(n, spec, TR.rng_for(3, 8)), TR.budgets(n, spec, TR.rng_for(4, 8))
    np.testing.assert_array_equal(np.sort(ba), np.sort(bb))
    assert int((ba == 0.5).sum()) == 45
