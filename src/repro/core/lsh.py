"""E2LSH index with a TPU-native sorted-CSR bucket layout.

Paper §2.2 / §4.2: ``h_{a,b}(o) = floor((a·o + b) / W)`` with ``a`` drawn from
N(0, I) (2-stable) and ``b ~ U[0, W)``. ``K`` functions form one table's
composite code; ``L`` independent tables form the index.

TPU adaptation (DESIGN.md §3): hashing is a single ``(N,d) @ (d, L·K)``
matmul; the C++ hash *table* becomes a dense layout per table:

  * ``order``          (L, C)       point ids sorted by bucket code
  * ``bucket_codes``   (L, B, K)    unique codes, row ``j`` = code of bucket j
  * ``bucket_starts``  (L, B)       CSR offset of bucket j into ``order``
  * ``bucket_sizes``   (L, B)       number of points in bucket j
  * ``n_buckets``      (L,)         number of valid bucket rows
  * ``n_valid``        ()           number of live points (<= capacity C)

Rows ``j >= n_buckets[l]`` are padding (size 0, code sentinel). The bucket
axis is padded to ``B_max = C`` while tracing (shard_map builds), but a
concrete *static* build TRIMS it to ``max(n_buckets)`` rounded up to a
multiple of 256 (DESIGN.md §9) — real indexes use a fraction of C buckets,
and every per-query op on the bucket axis (Hamming compare, ring cumsums,
searchsorted) scales with the padded size.

Capacity padding (DESIGN.md §10): arrays are sized to a *capacity* C that
may exceed the live point count ``n_valid``. Padding point rows carry
``CODE_SENTINEL`` codes, so after the lexsort they collapse into one
trailing sentinel bucket at row ``n_buckets`` — masked out of every probe by
the existing ``j < n_buckets`` convention. A capacity-padded index keeps the
bucket axis untrimmed (B = C) so in-capacity dynamic updates are fixed-shape
jitted steps (updates.py) that never recompile; capacity grows by amortized
doubling (:func:`grow_capacity`), recompiling once per doubling.

Raw projections are retained so dynamic updates can recompute ``W`` exactly
as paper Alg. 7 (``normalizeW``). ``LSHIndex.raw`` stores the PURE
projection ``a·x`` (no ``b·W`` offset): the offset is a per-function
constant, so it cancels out of Alg. 7's ``hi - lo`` mathematically — and
keeping it out of the stored array makes it cancel *bitwise* too. An ingest
that extends no projection extreme then reproduces ``W`` exactly (no
ulp-level drift from re-adding a rescaled offset), which is what lets the
serving cache's epoch invalidation (DESIGN.md §12) treat "W unchanged" as
"code geometry unchanged" instead of flushing on every ingest.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.config import ProberConfig

CODE_SENTINEL = jnp.iinfo(jnp.int32).max


class LSHParams(NamedTuple):
    """The hash functions themselves — shared by every shard of a
    distributed index so codes are globally consistent."""
    a: jax.Array   # (d, L*K) float32, N(0,1) entries
    b: jax.Array   # (L*K,)  float32, U[0, W) at init (rescaled with W)
    w: jax.Array   # (L*K,)  float32, per-function bucket width


class LSHIndex(NamedTuple):
    params: LSHParams
    raw: jax.Array            # (C, L*K) float32 — a·x + b (pre division)
    codes: jax.Array          # (L, C, K) int32 — per-point codes (padding
                              #   rows hold CODE_SENTINEL)
    order: jax.Array          # (L, C) int32 — points sorted by bucket
    bucket_codes: jax.Array   # (L, B, K) int32 — unique codes (padded)
    bucket_starts: jax.Array  # (L, B) int32
    bucket_sizes: jax.Array   # (L, B) int32
    n_buckets: jax.Array      # (L,) int32
    n_valid: jax.Array        # () int32 — live points (rows < n_valid)

    @property
    def capacity(self) -> int:
        return self.raw.shape[0]

    @property
    def n_points(self) -> int:
        """Static row capacity of the layout (== live count for a plain
        build; live count is the ``n_valid`` array for padded indexes)."""
        return self.raw.shape[0]

    @property
    def n_tables(self) -> int:
        return self.codes.shape[0]

    @property
    def n_funcs(self) -> int:
        return self.codes.shape[2]


def init_params(key: jax.Array, dim: int, cfg: ProberConfig) -> LSHParams:
    """Sample the (L·K) hash functions. ``w`` starts at 1 and is normalised
    against the data by :func:`normalize_w` during the build."""
    lk = cfg.n_tables * cfg.n_funcs
    ka, kb = jax.random.split(key)
    a = jax.random.normal(ka, (dim, lk), dtype=jnp.float32)
    b = jax.random.uniform(kb, (lk,), dtype=jnp.float32)  # in [0,1); scaled by w
    w = jnp.ones((lk,), dtype=jnp.float32)
    return LSHParams(a=a, b=b, w=w)


def project(params: LSHParams, x: jax.Array) -> jax.Array:
    """Offset projections ``a·x + b·w`` of shape (..., L*K) — what
    :func:`quantize` divides by ``w`` to get bucket ids.

    ``b`` is stored as a fraction of ``w`` so that re-normalising ``w``
    (paper Alg. 7) keeps the offset a valid U[0, W) sample.
    """
    return project_raw(params, x) + params.b * params.w


def project_raw(params: LSHParams, x: jax.Array) -> jax.Array:
    """Pure projections ``a·x`` (..., L*K) — offset-free, so independent of
    ``w``. This is what the index retains (``LSHIndex.raw``) and what
    Alg. 7's ``normalizeW`` reduces over: min/max of ``a·x`` are exactly
    reproducible across ingests, so ``W`` only moves when an extreme
    actually moves (see module docstring).

    ``x`` may be wider than the d rows of ``a``: the stored corpus carries
    zero columns past d (``estimator.stored_width``), which meet zero rows
    of ``a`` and add nothing."""
    a = params.a
    if x.shape[-1] > a.shape[0]:
        a = jnp.pad(a, ((0, x.shape[-1] - a.shape[0]), (0, 0)))
    return x.astype(jnp.float32) @ a


def normalize_w(raw: jax.Array, n_regions: int,
                n_valid: jax.Array | None = None,
                axis_name=None) -> jax.Array:
    """Paper Alg. 7 ``normalizeW``: per-function width from the min/max of the
    raw (pure ``a·x``) projections so each function yields ~``n_regions``
    distinct values. Offset-free inputs make the result bitwise-reproducible
    across ingests whose points extend no extreme (module docstring).

    ``n_valid`` masks capacity-padding rows (DESIGN.md §10) out of the
    min/max so dead rows never influence the bucket widths. Under shard_map
    (DESIGN.md §4) ``axis_name`` pools the extremes across the data shards
    with a pmin/pmax, so a sharded ingest renormalises ``W`` from the
    min/max of ALL live projections — exactly the global Alg. 7 semantics —
    and every shard keeps bit-identical hash functions.
    """
    if n_valid is None:
        lo = jnp.min(raw, axis=0)
        hi = jnp.max(raw, axis=0)
    else:
        valid = (jnp.arange(raw.shape[0]) < n_valid)[:, None]
        lo = jnp.min(jnp.where(valid, raw, jnp.inf), axis=0)
        hi = jnp.max(jnp.where(valid, raw, -jnp.inf), axis=0)
    if axis_name is not None:
        lo = jax.lax.pmin(lo, axis_name)
        hi = jax.lax.pmax(hi, axis_name)
    return jnp.maximum((hi - lo) / float(n_regions), 1e-6)


def quantize(raw: jax.Array, w: jax.Array) -> jax.Array:
    """``floor(raw / W)`` — the E2LSH bucket id per function."""
    return jnp.floor(raw / w).astype(jnp.int32)


def hash_point(params: LSHParams, x: jax.Array, n_tables: int) -> jax.Array:
    """Hash one point (or batch) → (..., L, K) int32 codes."""
    raw = project(params, x)
    codes = quantize(raw, params.w)
    return codes.reshape(*x.shape[:-1], n_tables, -1)


_PACK_BITS = 6            # per-column field width for the packed fast path
_PACK_COLS = 30 // _PACK_BITS   # columns per uint32 key word (30 bits used)


def _pack_fits(codes: jax.Array,
               valid: jax.Array | None = None) -> jax.Array:
    """Scalar predicate: every column's live code range fits the packed
    6-bit sort field. ``codes`` is (..., N, K) — the reduction spans every
    leading (table) axis so ONE unbatched boolean can steer the
    ``lax.cond`` in :func:`lexsort_rows` under vmap (a batched predicate
    would make vmap execute BOTH branches and pay the K-pass fallback on
    every call)."""
    if valid is None:
        lo = jnp.min(codes, axis=-2)
        hi = jnp.max(codes, axis=-2)
    else:
        imax, imin = jnp.iinfo(jnp.int32).max, jnp.iinfo(jnp.int32).min
        v = valid[:, None]
        lo = jnp.min(jnp.where(v, codes, imax), axis=-2)
        hi = jnp.max(jnp.where(v, codes, imin), axis=-2)
    # float diff: an int32 subtraction could wrap for sentinel-sized ranges
    rng = hi.astype(jnp.float32) - lo.astype(jnp.float32)
    return jnp.all(rng < (1 << _PACK_BITS)) & jnp.all(rng >= 0)


def lexsort_rows(codes: jax.Array,
                 valid: jax.Array | None = None,
                 fits: jax.Array | None = None) -> jax.Array:
    """Return a permutation sorting rows of ``codes`` (N, K) lexicographically.

    Fast path (the ingest hot loop, DESIGN.md §10): E2LSH codes under
    ``normalizeW`` span only ~``n_regions`` values per function, so each
    column is rank-compressed to a 6-bit field and 5 columns pack into one
    uint32 sort key — ONE stable ``lax.sort`` on ``ceil(K/5)`` key words
    replaces K column passes. Rows masked out by ``valid`` (capacity
    padding) are excluded from the range check and get all-ones keys, so
    they sort past every live row — exactly where their ``CODE_SENTINEL``
    codes would land. A ``lax.cond`` falls back to the always-correct
    K-pass column sort when any live column's range exceeds the field
    (both branches compile once; the data picks at run time). Vmapped
    callers (the per-table build) must pass an UNBATCHED ``fits``
    (:func:`_pack_fits` over all tables at once) so the cond stays a real
    branch under vmap.
    """
    n, k = codes.shape
    perm = jnp.arange(n, dtype=jnp.int32)

    def generic(_):
        p = perm
        for col in range(k - 1, -1, -1):
            keys = codes[p, col]
            _, p = jax.lax.sort((keys, p), is_stable=True, num_keys=1)
        return p

    nkeys = -(-k // _PACK_COLS)
    if nkeys > 4:                       # huge K: packing saves little
        return generic(None)

    if fits is None:
        fits = _pack_fits(codes, valid)
    if valid is None:
        lo = jnp.min(codes, axis=0)
    else:                               # dead rows don't constrain the range
        lo = jnp.min(jnp.where(valid[:, None], codes,
                               jnp.iinfo(jnp.int32).max), axis=0)

    def packed(_):
        shifted = jnp.clip(codes - lo[None, :], 0,
                           (1 << _PACK_BITS) - 1).astype(jnp.uint32)
        dead = jnp.zeros((n,), jnp.bool_) if valid is None else ~valid
        keys = []
        for g in range(nkeys):
            cols = shifted[:, g * _PACK_COLS:(g + 1) * _PACK_COLS]
            acc = jnp.zeros((n,), jnp.uint32)
            for j in range(cols.shape[1]):
                acc = (acc << _PACK_BITS) | cols[:, j]
            keys.append(jnp.where(dead, jnp.uint32(0xFFFFFFFF), acc))
        out = jax.lax.sort((*keys, perm), is_stable=True, num_keys=nkeys)
        return out[-1]

    return jax.lax.cond(fits, packed, generic, None)


def _build_table(codes_t: jax.Array,
                 n_valid: jax.Array | None = None,
                 fits: jax.Array | None = None) -> tuple[jax.Array, ...]:
    """Build one table's sorted-CSR layout from (C, K) codes.

    With ``n_valid`` (DESIGN.md §10), rows ``>= n_valid`` are capacity
    padding: their codes are forced to ``CODE_SENTINEL`` so they lexsort
    past every live code into a single trailing sentinel bucket, and
    ``n_buckets`` counts live buckets only — the sentinel bucket lands at
    row ``n_buckets`` where the ``j < n_buckets`` probe mask ignores it.
    """
    n = codes_t.shape[0]
    valid = None
    if n_valid is not None:
        valid = jnp.arange(n) < n_valid
        codes_t = jnp.where(valid[:, None], codes_t, CODE_SENTINEL)
    perm = lexsort_rows(codes_t, valid=valid, fits=fits)
    sorted_codes = codes_t[perm]
    # boundary[i] = 1 iff row i starts a new bucket
    prev = jnp.concatenate([sorted_codes[:1] - 1, sorted_codes[:-1]], axis=0)
    boundary = jnp.any(sorted_codes != prev, axis=-1)
    bucket_of_row = jnp.cumsum(boundary) - 1            # (C,) 0-based bucket id
    if n_valid is None:
        n_buckets = bucket_of_row[-1] + 1
    else:
        last = bucket_of_row[jnp.maximum(n_valid - 1, 0)]
        n_buckets = jnp.where(n_valid > 0, last + 1, 0)
    # CSR: starts[j] = first row of bucket j (seed with N so .min works);
    # sizes via scatter-add
    starts = jnp.full((n,), n, jnp.int32).at[bucket_of_row].min(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    sizes = jnp.zeros((n,), jnp.int32).at[bucket_of_row].add(1, mode="drop")
    bucket_codes = jnp.full_like(sorted_codes, CODE_SENTINEL)
    bucket_codes = bucket_codes.at[bucket_of_row].set(sorted_codes, mode="drop")
    return perm.astype(jnp.int32), bucket_codes, starts, sizes, n_buckets.astype(jnp.int32)


def build_index(x: jax.Array, cfg: ProberConfig, key: jax.Array,
                params: LSHParams | None = None,
                n_valid: jax.Array | int | None = None,
                dim: int | None = None) -> LSHIndex:
    """Build the full L-table index over ``x`` (C, d).

    If ``params`` is given (distributed build / updates) the hash functions
    are reused; otherwise they are sampled and ``W`` normalised on ``x``.

    If ``n_valid`` is given (DESIGN.md §10), rows ``>= n_valid`` of ``x``
    are capacity padding: they are masked out of the W normalisation,
    their codes become ``CODE_SENTINEL``, and the bucket axis stays
    untrimmed (B = C) so the layout's shapes are a pure function of the
    capacity — the contract the jitted update steps rely on.

    ``dim`` is the dimension the hash functions are drawn in when ``x``
    carries zero columns past it (the stored layout, estimator.build).
    """
    nv = None if n_valid is None else jnp.asarray(n_valid, jnp.int32)
    if params is None:
        params = init_params(key, dim or x.shape[-1], cfg)
        raw = project_raw(params, x)                        # pure a·x
        params = params._replace(w=normalize_w(raw, cfg.n_regions, nv))
    else:
        raw = project_raw(params, x)
    n = x.shape[0]
    codes = quantize(raw + params.b * params.w, params.w)   # (C, L*K)
    codes = codes.reshape(n, cfg.n_tables, cfg.n_funcs)
    codes = jnp.swapaxes(codes, 0, 1)                       # (L, C, K)
    if nv is not None:
        codes = jnp.where((jnp.arange(n) < nv)[None, :, None], codes,
                          CODE_SENTINEL)
    fits = _pack_fits(codes, None if nv is None else (jnp.arange(n) < nv))
    order, bcodes, starts, sizes, nb = jax.vmap(
        _build_table, in_axes=(0, None, None))(codes, nv, fits)
    cap = _static_bucket_cap(nb, n) if nv is None else n
    return LSHIndex(params=params, raw=raw, codes=codes, order=order,
                    bucket_codes=bcodes[:, :cap], bucket_starts=starts[:, :cap],
                    bucket_sizes=sizes[:, :cap], n_buckets=nb,
                    n_valid=jnp.asarray(n if nv is None else nv, jnp.int32))


def _static_bucket_cap(n_buckets: jax.Array, n: int) -> int:
    """Static bucket-axis length: ``max(n_buckets)`` rounded up to a multiple
    of 256 (shape reuse across similar builds), or ``n`` while tracing —
    trimming needs a concrete value and padding to N is always correct."""
    try:
        m = int(jax.device_get(jnp.max(n_buckets)))
    except jax.errors.ConcretizationTypeError:
        return n
    return min(n, max(256, -(-m // 256) * 256))


def grow_capacity(index: LSHIndex, new_capacity: int) -> LSHIndex:
    """Re-pad an index to a larger capacity (DESIGN.md §10).

    The live rows keep their raw projections and codes verbatim; the new
    padding rows join the sentinel bucket. The bucket axis is widened to the
    new capacity (untrimmed), so the result is the fixed-shape layout the
    jitted ingest steps consume. Compiles once per capacity — amortized
    O(log N) compilations under doubling growth.
    """
    cap = index.raw.shape[0]
    assert new_capacity >= cap, (new_capacity, cap)
    pad = new_capacity - cap
    raw = jnp.pad(index.raw, ((0, pad), (0, 0)))
    codes = jnp.pad(index.codes, ((0, 0), (0, pad), (0, 0)),
                    constant_values=CODE_SENTINEL)
    fits = _pack_fits(codes, jnp.arange(new_capacity) < index.n_valid)
    order, bcodes, starts, sizes, nb = jax.vmap(
        _build_table, in_axes=(0, None, None))(codes, index.n_valid, fits)
    return LSHIndex(params=index.params, raw=raw, codes=codes, order=order,
                    bucket_codes=bcodes, bucket_starts=starts,
                    bucket_sizes=sizes, n_buckets=nb, n_valid=index.n_valid)


def hamming_to_buckets(bucket_codes: jax.Array, n_buckets: jax.Array,
                       qcode: jax.Array) -> jax.Array:
    """Hamming distance (paper Def. 6) from the query's code to every unique
    bucket code of one table. Padding rows get ``K+1`` (never probed).

    This one vectorised (B, K) compare-reduce *is* the neighbor lookup on
    TPU — rings N_k are recovered as ``dist == k`` masks (DESIGN.md §3).
    ``n_buckets`` excludes the capacity-padding sentinel bucket (DESIGN.md
    §10), so dead points can never join a ring.
    """
    k = bucket_codes.shape[-1]
    dist = jnp.sum(bucket_codes != qcode[None, :], axis=-1).astype(jnp.int32)
    valid = jnp.arange(bucket_codes.shape[0]) < n_buckets
    return jnp.where(valid, dist, k + 1)
