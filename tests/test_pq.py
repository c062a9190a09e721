"""Product quantization + ADC tests (paper §2.2/§4.6, Alg. 4/5/8)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pq as pqmod, updates
from repro.core.config import ProberConfig

CFG = ProberConfig(pq_m=4, pq_kc=16, pq_iters=10)


@pytest.fixture(scope="module")
def fitted():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (600, 32))
    return x, pqmod.fit(x, CFG, key)


def test_shapes(fitted):
    x, pq = fitted
    assert pq.centroids.shape == (4, 16, 8)
    assert pq.codes.shape == (600, 4)
    assert pq.resid.shape == (600,)
    assert float(jnp.sum(pq.counts)) == 600 * 4


def test_codes_are_nearest_centroids(fitted):
    x, pq = fitted
    again = pqmod.assign(pq.centroids, x)
    np.testing.assert_array_equal(np.asarray(again), np.asarray(pq.codes))


def test_adc_table_and_distance_consistent(fitted):
    """ADC distance == ||q - reconstruction||^2 exactly (Alg. 5)."""
    x, pq = fitted
    q = x[7] + 0.1
    lut = pqmod.adc_table(pq, q)
    d = pqmod.adc_distance(lut, pq.codes[:50])
    recon = pq.centroids[jnp.arange(4)[None], pq.codes[:50]]  # (50, 4, 8)
    manual = jnp.sum((pqmod.split_subspaces(x[:50] * 0 + q[None], 4)
                      - recon) ** 2, axis=(-1, -2))
    np.testing.assert_allclose(np.asarray(d), np.asarray(manual), rtol=1e-4)


def test_adc_band_property(fitted):
    """Triangle-inequality band: |sqrt(adc) - sqrt(true)| <= resid, always."""
    x, pq = fitted
    q = x[3]
    lut = pqmod.adc_table(pq, q)
    adc = np.asarray(pqmod.adc_distance(lut, pq.codes))
    true = np.asarray(jnp.sum((x - q[None]) ** 2, axis=-1))
    gap = np.abs(np.sqrt(adc) - np.sqrt(true))
    assert (gap <= np.asarray(pq.resid) + 1e-3).all()


def test_adc_approximates_true_distance_structured():
    """On low-intrinsic-dim data (where distances have spread — isotropic
    Gaussians concentrate and defeat any quantizer) ADC correlates
    strongly with true distance."""
    from repro.data import vectors
    key = jax.random.PRNGKey(0)
    x = vectors.make_corpus(key, 2000, 64)
    cfg = ProberConfig(pq_m=16, pq_kc=32, pq_iters=10)
    pq = pqmod.fit(x, cfg, key)
    q = x[3]
    lut = pqmod.adc_table(pq, q)
    adc = np.asarray(pqmod.adc_distance(lut, pq.codes))
    true = np.asarray(jnp.sum((x - q[None]) ** 2, axis=-1))
    assert np.corrcoef(adc, true)[0, 1] > 0.9


def test_update_pq_running_means(fitted):
    """Alg. 8: counts accumulate; centroids move toward the new mass."""
    x, pq = fitted
    key = jax.random.PRNGKey(9)
    x_new = jax.random.normal(key, (200, 32)) + 2.0
    pq2 = updates.update_pq(pq, x_new, jnp.concatenate([x, x_new], axis=0))
    assert pq2.codes.shape == (800, 4)
    assert int(pq2.n_valid) == 800
    assert float(jnp.sum(pq2.counts)) == 800 * 4
    assert pq2.resid.shape == (800,)
    # new points' codes are nearest of the OLD centroids (paper's rule)
    np.testing.assert_array_equal(
        np.asarray(pqmod.assign(pq.centroids, x_new)),
        np.asarray(pq2.codes[600:]))


def test_update_pq_residuals_consistent_after_centroid_move(fitted):
    """Regression: the incremental-mean update moves centroids, so EVERY
    live point's stored residual must equal ||x - q(x)|| under the moved
    codebook — old points used to keep pre-update residuals."""
    x, pq = fitted
    x_new = jax.random.normal(jax.random.PRNGKey(5), (150, 32)) + 1.5
    x_all = jnp.concatenate([x, x_new], axis=0)
    pq2 = updates.update_pq(pq, x_new, x_all)
    want = pqmod.reconstruction_residual(
        pq2.centroids, pq2.codes.astype(jnp.int32), x_all)
    np.testing.assert_allclose(np.asarray(pq2.resid), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    # and the old points' centroids really did move (the test's premise)
    assert float(jnp.max(jnp.abs(pq2.centroids - pq.centroids))) > 1e-3


def test_update_equivalent_mass():
    """Counts-weighted incremental mean == batch mean when assignments are
    held fixed."""
    key = jax.random.PRNGKey(1)
    x1 = jax.random.normal(key, (100, 8))
    cfg = ProberConfig(pq_m=2, pq_kc=4, pq_iters=5)
    pq1 = pqmod.fit(x1, cfg, key)
    x2 = jax.random.normal(jax.random.PRNGKey(2), (50, 8)) * 0.1
    pq2 = updates.update_pq(pq1, x2, jnp.concatenate([x1, x2], axis=0))
    # manual: c' = (c*n + sum_new)/(n + n_new) per (m, k)
    xs = pqmod.split_subspaces(x2, 2)
    codes = pqmod.assign(pq1.centroids, x2)
    for m in range(2):
        for k in range(4):
            mask = np.asarray(codes[:, m]) == k
            n_old = float(pq1.counts[m, k])
            if mask.sum() == 0:
                np.testing.assert_allclose(np.asarray(pq2.centroids[m, k]),
                                           np.asarray(pq1.centroids[m, k]),
                                           rtol=1e-5)
                continue
            s = np.asarray(xs[:, m][mask]).sum(0)
            want = (np.asarray(pq1.centroids[m, k]) * n_old + s) / (n_old + mask.sum())
            np.testing.assert_allclose(np.asarray(pq2.centroids[m, k]), want,
                                       rtol=1e-4)


# --- the row-blocked build against the padded-copy build it replaced ------

ROWS = 256       # block rows here: 1000 points make 3 blocks and a tail


def _padded_blocks(a, rows, fill=0):
    """The former build's input: a padded copy cut into row blocks."""
    pad = (-a.shape[0]) % rows
    a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                constant_values=fill)
    return a.reshape(-1, rows, *a.shape[1:])


def _padded_assign(centroids, xs):
    n, m, ds = xs.shape
    rows = min(n, ROWS)
    codes = jax.lax.map(
        lambda b: pqmod._assign_block(centroids, b.reshape(rows, m, ds)),
        _padded_blocks(xs.reshape(n, -1), rows))
    return codes.reshape(-1, m)[:n]


def _padded_sums(xs, codes, kc):
    n, m, ds = xs.shape
    rows = min(n, ROWS)
    offs = (jnp.arange(m, dtype=jnp.int32) * kc)[None, :]

    def one(acc, blk):
        xb, cb = blk
        seg = (cb + offs).reshape(-1)
        sums = jax.ops.segment_sum(xb.reshape(rows * m, ds), seg,
                                   num_segments=m * kc)
        cnts = jax.ops.segment_sum(jnp.ones(seg.shape, jnp.float32), seg,
                                   num_segments=m * kc)
        return (acc[0] + sums, acc[1] + cnts), None

    init = (jnp.zeros((m * kc, ds), xs.dtype),
            jnp.zeros((m * kc,), jnp.float32))
    (sums, cnts), _ = jax.lax.scan(
        one, init, (_padded_blocks(xs.reshape(n, -1), rows),
                    _padded_blocks(codes, rows, fill=m * kc)))
    return sums.reshape(m, kc, ds), cnts.reshape(m, kc)


def _padded_resid(centroids, codes, xs):
    n, m, ds = xs.shape
    rows = min(n, ROWS)

    def one(blk):
        cb, xb = blk
        recon = centroids[jnp.arange(m)[None, :], cb]
        return jnp.sqrt(jnp.sum((xb.reshape(rows, m, ds) - recon) ** 2,
                                axis=(-1, -2)))

    return jax.lax.map(one, (_padded_blocks(codes, rows),
                             _padded_blocks(xs.reshape(n, -1), rows))
                       ).reshape(-1)[:n]


@jax.jit
def _padded_fit(x, key):
    m, kc = BLOCK_CFG.pq_m, BLOCK_CFG.pq_kc
    xs = pqmod.split_subspaces(x, m)
    init_rows = jax.random.choice(key, x.shape[0], (kc,), replace=False)
    centroids = jnp.swapaxes(xs[init_rows], 0, 1)

    def step(c, _):
        sums, cnts = _padded_sums(xs, _padded_assign(c, xs), kc)
        return jnp.where(cnts[..., None] > 0,
                         sums / jnp.maximum(cnts[..., None], 1.0), c), None

    centroids, _ = jax.lax.scan(step, centroids, None,
                                length=BLOCK_CFG.pq_iters)
    codes = _padded_assign(centroids, xs)
    _, counts = _padded_sums(xs, codes, kc)
    return (centroids, codes.astype(jnp.uint8), counts,
            _padded_resid(centroids, codes, xs))


BLOCK_CFG = ProberConfig(pq_m=8, pq_kc=16, pq_iters=3)


@pytest.mark.parametrize("d", [128, 120])
def test_blocked_build_equals_padded_copy_bit_for_bit(d, monkeypatch):
    """The build reads row blocks of the corpus in place (the last one
    partial and masked) where it used to cut a padded copy: centroids,
    codes, counts and residuals are bit-identical, for a plain fit and
    for the capacity-padded corpus stored 128 wide (as estimator.build
    runs kmeans and encode on it), and so are assign, _centroid_sums and
    reconstruction_residual on their own."""
    from repro.core import estimator as E
    monkeypatch.setattr(pqmod, "ASSIGN_ROWS", ROWS)
    n, cap, key = 1000, 2048, jax.random.PRNGKey(3)
    x = jax.random.normal(jax.random.PRNGKey(d), (n, d))
    stored = jnp.pad(x, ((0, cap - n), (0, E.stored_width(d) - d)))
    want = [np.asarray(a) for a in _padded_fit(x, key)]
    plain = pqmod.fit(x, BLOCK_CFG, key)
    padded = pqmod.encode(stored, pqmod.kmeans(stored, BLOCK_CFG, key,
                                               n_valid=n, dim=d),
                          BLOCK_CFG, n_valid=n)
    for pq, rows in ((plain, n), (padded, cap)):
        assert pq.codes.shape[0] == pq.resid.shape[0] == rows
        for w, g in zip(want, (pq.centroids, pq.codes, pq.counts, pq.resid)):
            np.testing.assert_array_equal(np.asarray(g)[:len(w)], w)
        assert not np.asarray(pq.codes[n:]).any()      # as grow pads them
        assert not np.asarray(pq.resid[n:]).any()

    c = plain.centroids
    xs = pqmod.split_subspaces(x, BLOCK_CFG.pq_m)
    codes = _padded_assign(c, xs)
    for xin in (x, stored[:n]):
        np.testing.assert_array_equal(np.asarray(pqmod.assign(c, xin)),
                                      np.asarray(codes))
        for got, w in zip(pqmod._centroid_sums(xin, codes, 16, d // 8, n),
                          _padded_sums(xs, codes, 16)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(w))
        np.testing.assert_array_equal(
            np.asarray(pqmod.reconstruction_residual(c, codes, xin)),
            np.asarray(_padded_resid(c, codes, xs)))
