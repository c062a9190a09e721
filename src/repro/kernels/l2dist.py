"""Blocked squared-L2 distance kernel: ``(N,d) × (Q,d) → (N,Q)``.

The online-estimation hot spot (paper §4.4: "distance computation is the
bottleneck"). Uses the MXU via ``d² = ‖x‖² − 2 x·qᵀ + ‖q‖²`` — one matmul per
(bn, bq) tile plus cheap rank-1 corrections, instead of the VPU-bound
elementwise (x−q)² reduce.

Grid: (N/bn, Q/bq); the contraction dim d stays resident per tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, q_ref, out_ref):
    x = x_ref[...]                     # (bn, d)
    q = q_ref[...]                     # (bq, d)
    xq = jnp.dot(x, q.T, preferred_element_type=jnp.float32)   # (bn, bq) MXU
    x2 = jnp.sum(x * x, axis=-1, keepdims=True)                # (bn, 1)
    q2 = jnp.sum(q * q, axis=-1, keepdims=True).T              # (1, bq)
    out_ref[...] = x2 - 2.0 * xq + q2


@functools.partial(jax.jit, static_argnames=("bn", "bq", "interpret"))
def l2dist(x: jax.Array, q: jax.Array, *, bn: int = 256, bq: int = 128,
           interpret: bool = False) -> jax.Array:
    """x (N, d), q (Q, d) → squared distances (N, Q) float32."""
    n, d = x.shape
    nq = q.shape[0]
    bn = min(bn, n)
    bq = min(bq, nq)
    pad_n = (-n) % bn
    pad_q = (-nq) % bq
    xp = jnp.pad(x, ((0, pad_n), (0, 0)))
    qp = jnp.pad(q, ((0, pad_q), (0, 0)))
    grid = (xp.shape[0] // bn, qp.shape[0] // bq)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((bn, bq), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], qp.shape[0]), jnp.float32),
        interpret=interpret,
    )(xp, qp)
    return out[:n, :nq]
