"""Fused E2LSH hashing kernel: ``floor((x @ a + b*w) / w)`` → int32 codes.

Hashing is the first hot loop of both the offline build and every online
query (paper §4.2). The matmul runs on the MXU; quantization fuses into the
same VMEM tile so raw projections never round-trip through HBM.

Grid: (N/bn, F/bf). Block shapes are MXU-aligned (multiples of 128 where the
problem allows). ``d`` (the contraction dim) stays unblocked — the largest
assigned corpus dim (1770) keeps an (bn, d) tile ≤ 2 MiB in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, a_ref, b_ref, w_ref, out_ref):
    x = x_ref[...]                     # (bn, d)
    a = a_ref[...]                     # (d, bf)
    proj = jnp.dot(x, a, preferred_element_type=jnp.float32)
    b = b_ref[...]                     # (bf,)
    w = w_ref[...]                     # (bf,)
    out_ref[...] = jnp.floor((proj + b[None, :] * w[None, :]) / w[None, :]
                             ).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bn", "bf", "interpret"))
def lsh_hash(x: jax.Array, a: jax.Array, b: jax.Array, w: jax.Array,
             *, bn: int = 256, bf: int = 128, interpret: bool = False
             ) -> jax.Array:
    """x (N, d), a (d, F), b (F,), w (F,) → codes (N, F) int32."""
    n, d = x.shape
    f = a.shape[1]
    bn = min(bn, n)
    bf = min(bf, f)
    pad_n = (-n) % bn
    pad_f = (-f) % bf
    xp = jnp.pad(x, ((0, pad_n), (0, 0)))
    ap = jnp.pad(a, ((0, 0), (0, pad_f)))
    bp = jnp.pad(b, (0, pad_f))
    wp = jnp.pad(w, (0, pad_f), constant_values=1.0)
    grid = (xp.shape[0] // bn, ap.shape[1] // bf)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, d), lambda i, j: (i, 0)),
            pl.BlockSpec((d, bf), lambda i, j: (0, j)),
            pl.BlockSpec((bf,), lambda i, j: (j,)),
            pl.BlockSpec((bf,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((bn, bf), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], ap.shape[1]), jnp.int32),
        interpret=interpret,
    )(xp, ap, bp, wp)
    return out[:n, :f]
