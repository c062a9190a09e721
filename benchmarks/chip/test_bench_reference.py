"""The exact reference against a plain numpy count."""
import numpy as np

from benchmarks.chip import reference


def numpy_count(x, qs, taus, limit=None):
    d2 = ((x[None].astype(np.float64) - qs[:, None].astype(np.float64))
          ** 2).sum(-1)
    hit = d2 <= taus[:, None].astype(np.float64) ** 2
    if limit is not None:
        hit &= np.arange(len(x))[None, :] < limit[:, None]
    return hit.sum(-1)


def test_reference_matches_numpy_count():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1000, 24)).astype(np.float32)
    qs = x[rng.choice(1000, 37, replace=False)]
    d = np.sort(((x[None] - qs[:, None]) ** 2).sum(-1), axis=1)
    k = rng.integers(1, 900, 37)
    # radii between two distances, so float rounding cannot flip a count
    taus = np.sqrt(0.5 * (d[np.arange(37), k - 1] + d[np.arange(37), k]))
    got = reference.exact_counts(x, qs, taus, q_block=8, row_block=128)
    np.testing.assert_array_equal(got, numpy_count(x, qs, taus))
    np.testing.assert_array_equal(got, k)


def test_reference_counts_only_visible_rows():
    rng = np.random.default_rng(1)
    x = rng.standard_normal((300, 8)).astype(np.float32)
    qs = rng.standard_normal((10, 8)).astype(np.float32)
    taus = np.full(10, 3.0, np.float32)
    limit = rng.integers(0, 301, 10)
    got = reference.exact_counts(x, qs, taus, limit=limit, row_block=64)
    np.testing.assert_array_equal(got, numpy_count(x, qs, taus, limit))
    assert reference.exact_counts(x, qs[:0], taus[:0]).shape == (0,)
