"""Synthetic vector corpora shaped like the paper's five datasets.

The container is offline, so SIFT/GloVe/FastText/GIST/YouTube are replaced by
*matched-shape surrogates* (DESIGN.md §8): ambient dimension matches the real
corpus; N is scaled to the CPU budget; the geometry is a clustered **low
intrinsic dimensional manifold** (real image/text embeddings have intrinsic
dim ~8–20), which gives broad distance distributions — unlike isotropic
Gaussians whose distances concentrate in a thin shell and defeat every
approximate method (including the paper's).

Query workload follows the paper's protocol (§6.1 Query Selection): sample
query points from the data, pick a geometric sequence of target cardinalities
in [1, min(20000, 1% N)], and set tau per (query, target) as the minimal
threshold achieving that cardinality (computed from exact sorted distances).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

# name -> (n_objects, dim) at benchmark scale (real-corpus dims, CPU-scaled N)
CORPORA: Dict[str, tuple[int, int]] = {
    "sift":     (40000, 128),
    "glove":    (40000, 300),
    "fasttext": (40000, 300),
    "gist":     (20000, 960),
    "youtube":  (10000, 1770),
}


@dataclasses.dataclass(frozen=True)
class VectorDataset:
    name: str
    x: jax.Array            # (N, d)
    queries: jax.Array      # (Q, d)
    taus: jax.Array         # (Q, T) threshold grid per query
    cards: jax.Array        # (Q, T) exact cardinality per (query, tau)


def make_corpus(key: jax.Array, n: int, dim: int, *, n_clusters: int = 32,
                intrinsic_dim: int = 12, noise: float = 0.05) -> jax.Array:
    """Clustered low-intrinsic-dim manifold embedded in R^dim."""
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    basis = jax.random.normal(k1, (intrinsic_dim, dim)) / np.sqrt(intrinsic_dim)
    centers = jax.random.normal(k2, (n_clusters, intrinsic_dim)) * 2.0
    # heavy-tailed per-cluster scales (paper datasets are highly non-uniform)
    scales = jnp.exp(jax.random.normal(k3, (n_clusters,)) * 0.8)
    assign = jax.random.randint(k4, (n,), 0, n_clusters)
    z = centers[assign] + jax.random.normal(k5, (n, intrinsic_dim)) * scales[assign, None]
    x = z @ basis
    x = x + jax.random.normal(k1, (n, dim)) * noise   # ambient noise
    return x.astype(jnp.float32)


@jax.jit
def _taus_and_cards(x: jax.Array, queries: jax.Array, targets: jax.Array):
    """Per query: the tau grid reaching each target cardinality, and the
    exact count at each tau. ``x`` is an argument, not a closed-over
    constant, so the corpus never becomes part of the compiled program."""
    n = x.shape[0]

    def taus_for(q):
        d2 = jnp.sum((x - q[None, :]) ** 2, axis=-1)
        d2s = jnp.sort(d2)
        # minimal tau reaching each target cardinality; midpoint to the next
        # distinct distance so ties don't flip the exact count
        at = jnp.sqrt(d2s[targets - 1])
        nxt = jnp.sqrt(d2s[jnp.minimum(targets, n - 1)])
        return jnp.where(targets < n, 0.5 * (at + nxt), at + 1e-3)

    def card_for(qt):
        q, ts = qt
        d2 = jnp.sum((x - q[None, :]) ** 2, axis=-1)
        return jnp.sum(d2[None, :] <= (ts ** 2)[:, None], axis=-1)

    taus = jax.lax.map(taus_for, queries)
    return taus, jax.lax.map(card_for, (queries, taus))


def paper_query_workload(key: jax.Array, x: jax.Array, n_queries: int,
                         n_taus: int = 12, max_card: int | None = None):
    """Paper §6.1: geometric target-cardinality grid, tau = minimal threshold.

    Returns (queries (Q,d), taus (Q,T), cards (Q,T)).
    """
    n = x.shape[0]
    if max_card is None:
        max_card = min(20000, max(n // 100, 2))
    qidx = jax.random.choice(key, n, (n_queries,), replace=False)
    queries = x[qidx]
    targets = np.unique(np.geomspace(1, max_card, n_taus).astype(np.int64))
    taus, cards = _taus_and_cards(x, queries, jnp.asarray(targets))
    return queries, taus, cards


def default_seed(name: str) -> int:
    """Seed of a corpus loaded without a key: a stable function of its
    name (``hash`` of a str differs per process under PYTHONHASHSEED)."""
    return zlib.crc32(name.encode()) % (2 ** 31)


def load(name: str, key: jax.Array | None = None, n_queries: int = 32,
         scale: float = 1.0) -> VectorDataset:
    """Build a named surrogate corpus + paper-protocol query workload."""
    if key is None:
        key = jax.random.PRNGKey(default_seed(name))
    n, dim = CORPORA[name]
    n = int(n * scale)
    kx, kq = jax.random.split(key)
    x = make_corpus(kx, n, dim)
    queries, taus, cards = paper_query_workload(kq, x, n_queries)
    return VectorDataset(name=name, x=x, queries=queries, taus=taus, cards=cards)
