"""Plain exact count: the reference that decides ``correct``.

``|{p in corpus : ||p - q|| <= tau}|`` for every request, by brute force in
float32 on the device, in blocks of requests and of corpus rows so that it
fits beside nothing else. It imports nothing of the program under test.
The distance is the direct sum of squared differences, the same
arithmetic the query grid used to pick each radius, so a point at the
boundary is counted as the grid meant it.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


@partial(jax.jit, static_argnames=("row_block",))
def _count_block(x: jax.Array, n_rows: jax.Array, qs: jax.Array,
                 tau_sq: jax.Array, limit: jax.Array, row_block: int):
    """Counts for a block of requests over ``x`` (R, d): rows ``i`` count for
    request ``j`` when ``i < n_rows`` and ``i < limit[j]``."""
    r = x.shape[0] // row_block
    xb = x.reshape(r, row_block, x.shape[1])

    def body(acc, blk):
        i, rows = blk
        d2 = jnp.sum((rows[None, :, :] - qs[:, None, :]) ** 2, axis=-1)
        idx = i * row_block + jnp.arange(row_block)
        live = (idx[None, :] < n_rows) & (idx[None, :] < limit[:, None])
        return acc + jnp.sum((d2 <= tau_sq[:, None]) & live, axis=-1), None

    acc, _ = jax.lax.scan(body, jnp.zeros(qs.shape[0], jnp.int32),
                          (jnp.arange(r), xb))
    return acc


def exact_counts(x: jax.Array, qs: np.ndarray, taus: np.ndarray,
                 limit: np.ndarray | None = None, q_block: int = 16,
                 row_block: int = 8192) -> np.ndarray:
    """Exact counts of rows of ``x`` (N, d) within ``taus[j]`` of ``qs[j]``.

    ``limit[j]`` (optional) counts only the first ``limit[j]`` rows for
    request ``j`` — the rows visible to it, for a corpus that grows.
    Returns an int64 array of shape (len(qs),).
    """
    x = jnp.asarray(x, jnp.float32)
    n, d = x.shape
    m = len(qs)
    if m == 0:
        return np.zeros(0, np.int64)
    row_block = min(row_block, n)
    rows_pad = -n % row_block
    if rows_pad:
        x = jnp.pad(x, ((0, rows_pad), (0, 0)))
    limit = np.full(m, n, np.int64) if limit is None else np.asarray(limit)
    pad = -m % q_block
    qs_p = np.pad(np.asarray(qs, np.float32), ((0, pad), (0, 0)))
    tau_p = np.pad(np.asarray(taus, np.float32), (0, pad))
    lim_p = np.pad(limit.astype(np.int32), (0, pad))
    out = []
    for s in range(0, m + pad, q_block):
        t = jnp.asarray(tau_p[s:s + q_block])
        out.append(_count_block(x, jnp.int32(n), jnp.asarray(qs_p[s:s + q_block]),
                                t * t, jnp.asarray(lim_p[s:s + q_block]),
                                row_block))
    return np.asarray(jnp.concatenate(out))[:m].astype(np.int64)
