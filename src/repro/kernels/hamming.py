"""Hamming-ring kernel (paper Def. 6/7): distance of the query's hash code to
every unique bucket code — the online replacement for the neighbor lookup
table (DESIGN.md §3). One compare-reduce over a (bb, K) tile per grid step.

Padding rows (beyond ``n_buckets``) are masked to K+1 by the wrapper so they
never join any ring.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(codes_ref, qcode_ref, out_ref):
    codes = codes_ref[...]             # (bb, K) int32
    qcode = qcode_ref[...]             # (K,) int32
    out_ref[...] = jnp.sum((codes != qcode[None, :]).astype(jnp.int32), axis=-1)


@functools.partial(jax.jit, static_argnames=("bb", "interpret"))
def hamming(bucket_codes: jax.Array, qcode: jax.Array, *, bb: int = 1024,
            interpret: bool = False) -> jax.Array:
    """bucket_codes (B, K) int32, qcode (K,) → (B,) int32 distances."""
    b, k = bucket_codes.shape
    bb = min(bb, b)
    pad_b = (-b) % bb
    cp = jnp.pad(bucket_codes, ((0, pad_b), (0, 0)))
    grid = (cp.shape[0] // bb,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, k), lambda i: (i, 0)),
            pl.BlockSpec((k,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bb,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((cp.shape[0],), jnp.int32),
        interpret=interpret,
    )(cp, qcode)
    return out[:b]
