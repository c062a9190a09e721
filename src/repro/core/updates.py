"""Dynamic data updates (paper §5, Alg. 7/8/9) — recompile-free in-capacity
ingest over the capacity-padded layout (DESIGN.md §10).

* LSH (Alg. 7): hash new points with the *original* functions, re-normalise
  ``W`` from the min/max of ALL live raw projections (old + new — the
  retained ``raw`` array makes this exact), re-quantise and rebuild the
  sorted-CSR layout. The rebuild is one sort — on TPU that IS the hash-table
  update.
* PQ (Alg. 8): assign new points to their nearest existing centroids, move
  the affected centroids to the running mean (counts retained in the index),
  and refresh the quantization residuals of EVERY live point against the
  moved centroids — old points' residuals would otherwise silently refer to
  pre-update centroids and break the banded-ADC triangle bound.
* Neighbor table (Alg. 9): see neighbors.update — new-vs-old / new-vs-new
  blocks only; fixed-shape jittable once the code array is capacity-padded.

Shapes do NOT grow with N: new points are written into spare capacity rows
of the padded layout (`jnp.where`-masked scatters at traced ``n_valid``), so
an in-capacity update is ONE fixed-shape jitted step that never recompiles.
Only a capacity doubling (amortized O(log N) times over any stream) pays a
recompile, and the update batch is padded to a power of two so at most
``log2(batch)`` ingest shapes ever compile. Measured in
benchmarks/bench_updates.py (mirroring paper Fig. 6/7 + the amortized
incremental-throughput sweep).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.core import lsh, pq as pqmod
from repro.core.config import ProberConfig


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def next_capacity(cap: int, needed: int) -> int:
    """Amortized doubling: smallest power-of-two multiple of ``cap`` (at
    least 256) covering ``needed``."""
    cap = max(cap, 256)
    while cap < needed:
        cap *= 2
    return cap


def _write_rows(dst: jax.Array, src: jax.Array, start: jax.Array,
                n_new: jax.Array) -> jax.Array:
    """Scatter ``src[:n_new]`` into ``dst[start:start+n_new]``.

    ``start``/``n_new`` are traced scalars; rows of ``src`` beyond ``n_new``
    (the power-of-two batch padding) are routed out of bounds and dropped,
    so a padded batch can never clobber live or spare rows it doesn't own.
    """
    nn_pad = src.shape[0]
    slots = jnp.arange(nn_pad, dtype=jnp.int32)
    rows = jnp.where(slots < n_new, start + slots, dst.shape[0])
    return dst.at[rows].set(src, mode="drop")


# ------------------------------------------------------------- LSH (Alg. 7)

def _lsh_ingest(index: lsh.LSHIndex, x_new: jax.Array, n_new: jax.Array,
                cfg: ProberConfig, axis_name=None) -> lsh.LSHIndex:
    """Fixed-shape Alg. 7 step: all output shapes equal the input capacity.

    Requires spare capacity for ``x_new.shape[0]`` rows (the wrapper grows
    first). jit-compiled once per (capacity, batch) shape pair.

    ``axis_name`` (DESIGN.md §4): inside a shard_map over that mesh axis,
    the W renormalisation pools its min/max across shards (one pmin/pmax
    pair per ingest), so every shard derives the same global widths and
    bucket codes stay globally consistent.
    """
    params = index.params
    nv = index.n_valid
    raw_new = lsh.project_raw(params, x_new)          # pure a·x, w-free
    raw_all = _write_rows(index.raw, raw_new, nv, n_new)
    nv2 = nv + n_new
    # normalizeW over ALL live raw projections (old + new). ``raw`` is
    # offset-free, so when the batch extends no extreme this reproduces W
    # BITWISE — old points' codes below are then reproduced bitwise too,
    # which is what lets the serving cache treat "W unchanged" as "bucket
    # geometry unchanged" (DESIGN.md §12)
    w_new = lsh.normalize_w(raw_all, cfg.n_regions, nv2, axis_name=axis_name)
    params = params._replace(w=w_new)
    codes = lsh.quantize(raw_all + params.b * w_new, w_new)
    cap = raw_all.shape[0]
    codes = codes.reshape(cap, cfg.n_tables, cfg.n_funcs)
    codes = jnp.swapaxes(codes, 0, 1)
    codes = jnp.where((jnp.arange(cap) < nv2)[None, :, None], codes,
                      lsh.CODE_SENTINEL)
    fits = lsh._pack_fits(codes, jnp.arange(cap) < nv2)
    order, bcodes, starts, sizes, nb = jax.vmap(
        lsh._build_table, in_axes=(0, None, None))(codes, nv2, fits)
    return lsh.LSHIndex(params=params, raw=raw_all, codes=codes, order=order,
                        bucket_codes=bcodes, bucket_starts=starts,
                        bucket_sizes=sizes, n_buckets=nb, n_valid=nv2)


_lsh_ingest_jit = jax.jit(_lsh_ingest, static_argnames=("cfg", "axis_name"))


def _epoch_ingest(ep, index: lsh.LSHIndex, old_w: jax.Array,
                  n_new: jax.Array):
    """Fold one ingest into the cache-invalidation epoch state (DESIGN.md
    §12) inside the same fixed-shape step as the Alg. 7 rebuild — zero
    extra dispatches, zero-recompile contract intact.

    The per-bucket ingest signal needs no explicit counters: the rebuilt
    layout's ``bucket_sizes`` ARE the per-bucket epochs (populations are
    monotone under the §5 stream — see repro/cache/epochs.py). What must
    be tracked is the hash-function GENERATION: if Alg. 7 moved any width
    (``w != old_w`` — bitwise-exact thanks to the offset-free retained
    projections), every stored code may have shifted and the whole cache
    generation is retired via the params epoch.
    """
    from repro.cache import epochs as cache_epochs
    w_changed = jnp.any(index.params.w != old_w)
    return cache_epochs.ingest_bump(ep, n_new, w_changed)


def _pad_batch(x_new: jax.Array) -> tuple[jax.Array, jax.Array]:
    nn = x_new.shape[0]
    nn_pad = next_pow2(nn)
    x_pad = jnp.pad(jnp.asarray(x_new, jnp.float32),
                    ((0, nn_pad - nn), (0, 0)))
    return x_pad, jnp.asarray(nn, jnp.int32)


def update_lsh(index: lsh.LSHIndex, x_new: jax.Array,
               cfg: ProberConfig) -> lsh.LSHIndex:
    """Alg. 7. Returns an index whose live rows cover the concatenated
    dataset. In-capacity calls dispatch one cached jitted step (zero new
    compilations); otherwise capacity doubles first (amortized)."""
    nn = x_new.shape[0]
    nv = int(jax.device_get(index.n_valid))
    cap = index.raw.shape[0]
    if nv + nn > cap:
        index = lsh.grow_capacity(index, next_capacity(cap, nv + nn))
    x_pad, n_new = _pad_batch(x_new)
    return _lsh_ingest_jit(index, x_pad, n_new, cfg)


# -------------------------------------------------------------- PQ (Alg. 8)

def _pq_ingest(pq: pqmod.PQIndex, x_all: jax.Array, x_new: jax.Array,
               n_new: jax.Array) -> pqmod.PQIndex:
    """Fixed-shape Alg. 8 step over the capacity-padded code/resid arrays.

    ``x_all`` is the capacity-padded corpus WITH the new rows already
    written at ``[n_valid, n_valid + n_new)``, at its stored width (read
    on its first d columns) — needed because the moved
    centroids invalidate every affected point's stored residual, so all
    live residuals are recomputed against the post-update centroids.
    """
    m, kc = pq.m, pq.kc
    cap = pq.codes.shape[0]
    nn_pad = x_new.shape[0]
    xs_new = pqmod.split_subspaces(x_new, m)              # (Nn, M, ds)
    ds = xs_new.shape[-1]
    # paper's rule: new points take the nearest of the OLD centroids
    new_codes = pqmod.assign(pq.centroids, x_new)         # (Nn, M)
    wvalid = (jnp.arange(nn_pad) < n_new)
    seg = (new_codes + (jnp.arange(m, dtype=jnp.int32) * kc)[None, :]).reshape(-1)
    wf = jnp.repeat(wvalid.astype(jnp.float32), m)
    sums = jax.ops.segment_sum(xs_new.reshape(nn_pad * m, ds) * wf[:, None],
                               seg, num_segments=m * kc)
    cnts = jax.ops.segment_sum(wf, seg, num_segments=m * kc)
    sums = sums.reshape(m, kc, ds)
    cnts = cnts.reshape(m, kc)
    tot = pq.counts + cnts
    # running mean: c' = (c*old_count + sum_new) / (old_count + new_count)
    new_centroids = jnp.where(
        tot[..., None] > 0,
        (pq.centroids * pq.counts[..., None] + sums) / jnp.maximum(tot[..., None], 1.0),
        pq.centroids)
    codes = _write_rows(pq.codes, new_codes.astype(pq.codes.dtype),
                        pq.n_valid, n_new)
    packed = pq.packed
    if packed is not None:   # keep the 4-bit mirror in sync (DESIGN.md §11)
        packed = _write_rows(packed,
                             pqmod.pack_codes(new_codes.astype(jnp.uint8)),
                             pq.n_valid, n_new)
    nv2 = pq.n_valid + n_new
    # refresh EVERY live residual against the moved centroids — old points
    # would otherwise keep residuals of the pre-update codebook
    resid = pqmod.reconstruction_residual(new_centroids,
                                          codes.astype(jnp.int32), x_all)
    resid = jnp.where(jnp.arange(cap) < nv2, resid, 0.0)
    return pqmod.PQIndex(centroids=new_centroids, codes=codes, counts=tot,
                         resid=resid, n_valid=nv2, packed=packed)


_pq_ingest_jit = jax.jit(_pq_ingest)


def update_pq(pq: pqmod.PQIndex, x_new: jax.Array,
              x_all: jax.Array) -> pqmod.PQIndex:
    """Alg. 8: assign-new + incremental centroid means + residual refresh.

    ``x_all`` must be the full corpus (old points first, then ``x_new``),
    optionally capacity-padded; the PQ arrays are grown to match. Residuals
    of ALL live points are recomputed against the moved centroids.
    """
    nn = x_new.shape[0]
    nv = int(jax.device_get(pq.n_valid))
    cap = x_all.shape[0]
    assert nv + nn <= cap, (nv, nn, cap)
    x_all = jnp.asarray(x_all, jnp.float32)
    if cap < pq.codes.shape[0]:      # exact corpus against padded PQ arrays
        x_all = jnp.pad(x_all, ((0, pq.codes.shape[0] - cap), (0, 0)))
    elif pq.codes.shape[0] < cap:
        pq = pqmod.grow(pq, cap)
    x_pad, n_new = _pad_batch(x_new)
    return _pq_ingest_jit(pq, x_all, x_pad, n_new)
