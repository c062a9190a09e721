"""Q-error and percentile arithmetic."""
import numpy as np
import pytest

from benchmarks.chip import harness, stats


def test_qerror_clamps_at_one():
    np.testing.assert_allclose(stats.qerror([0, 2, 10, 5], [0, 1, 5, 10]),
                               [1, 2, 2, 2])


def test_stats_summary_and_percentile():
    """Every answer counts in the q-error numbers, and in its band."""
    s = harness.accuracy(np.array([1.0, 2.0, 4.0, 0.0]),
                         np.array([1.0, 1.0, 1.0, 50.0]),
                         {"small": [0, 20], "large": [20, None]})
    assert s["qerror_gmean.small"] == pytest.approx(2.0)
    assert s["qerror_gmean.large"] == pytest.approx(50.0)
    assert s["qerror_gmean"] == pytest.approx((2.0 * 4.0 * 50.0) ** 0.25)
    assert stats.percentile([1, 2, 3], 50) == 2.0
    assert stats.percentile([], 50) is None
