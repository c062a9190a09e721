"""Corpus and query-grid generator: the benchmark's own copy.

Copied from ``repro.data.vectors`` (``make_corpus`` and the tau grid of
``_taus_and_cards`` / ``paper_query_workload``) so that the yardstick stays
fixed when the program's copy changes. Everything runs on the device in
jitted calls from one key.

* :func:`make_corpus` — clustered low-intrinsic-dimension manifold embedded
  in ``R^d`` (the SIFT/GIST-shaped surrogate); unlike the original, the
  geometry and the points are drawn from two keys (the harness derives
  both from the configuration's ``data_seed``).
* :func:`query_grid` — paper §6.1 query selection: query points sampled
  from the corpus, a geometric grid of target cardinalities, and per
  (query, target) the minimal radius reaching it (midpoint to the next
  distance so ties cannot flip the count).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("n", "dim", "n_clusters", "intrinsic_dim",
                                   "noise"))
def make_corpus(k_shape: jax.Array, k_points: jax.Array, n: int, dim: int, *,
                n_clusters: int = 32, intrinsic_dim: int = 12,
                noise: float = 0.05) -> jax.Array:
    """Clustered low-intrinsic-dim manifold embedded in R^dim, (n, dim) f32.

    ``k_shape`` draws the deployment's geometry (the embedding basis, the
    cluster centres and their heavy-tailed scales); ``k_points`` draws the
    points on it (cluster of each point, its offset, the ambient noise)."""
    k1, k2, k3 = jax.random.split(k_shape, 3)
    k4, k5, k6 = jax.random.split(k_points, 3)
    basis = jax.random.normal(k1, (intrinsic_dim, dim)) / np.sqrt(intrinsic_dim)
    centers = jax.random.normal(k2, (n_clusters, intrinsic_dim)) * 2.0
    scales = jnp.exp(jax.random.normal(k3, (n_clusters,)) * 0.8)
    assign = jax.random.randint(k4, (n,), 0, n_clusters)
    z = centers[assign] + jax.random.normal(k5, (n, intrinsic_dim)) * \
        scales[assign, None]
    x = z @ basis
    x = x + jax.random.normal(k6, (n, dim)) * noise
    return x.astype(jnp.float32)


def targets(max_card: int, n_taus: int) -> np.ndarray:
    """The geometric grid of target cardinalities, 1..max_card."""
    return np.unique(np.geomspace(1, max_card, n_taus).astype(np.int64))


@partial(jax.jit, static_argnames=("block",))
def _taus(x: jax.Array, queries: jax.Array, tgt: jax.Array, block: int):
    """(Q, T) minimal radii reaching each target count, ``block`` queries
    at a time (one (block, N) distance matrix live)."""
    n = x.shape[0]

    def taus_for(q):
        d2s = jnp.sort(jnp.sum((x - q[None, :]) ** 2, axis=-1))
        at = jnp.sqrt(d2s[tgt - 1])
        nxt = jnp.sqrt(d2s[jnp.minimum(tgt, n - 1)])
        return jnp.where(tgt < n, 0.5 * (at + nxt), at + 1e-3)

    qb = queries.reshape(-1, block, queries.shape[-1])
    return jax.lax.map(jax.vmap(taus_for), qb).reshape(queries.shape[0], -1)


def query_grid(key: jax.Array, x: jax.Array, n_queries: int,
               tgt: np.ndarray, block: int = 8):
    """Paper §6.1 grid: ``(query_rows (Q,), queries (Q, d), taus (Q, T))``
    with ``Q = n_queries`` distinct corpus rows as queries."""
    rows = jax.random.choice(key, x.shape[0], (n_queries,), replace=False)
    queries = x[rows]
    pad = -n_queries % block
    qp = jnp.pad(queries, ((0, pad), (0, 0)))
    taus = _taus(x, qp, jnp.asarray(tgt, jnp.int32), block)[:n_queries]
    return rows, queries, taus
