"""Product quantization with asymmetric distance computation (paper §2.2/§4.6).

A vector is split into ``M`` subvectors of dim ``ds = d/M``; each subspace is
k-means-clustered into ``Kc`` centroids; a point is stored as its (M,) int32
codeword. ADC (Alg. 4/5): per query build a lookup table
``T[m, c] = ||q_m - centroid[m, c]||^2`` once, then every point distance is
``sum_m T[m, code[p, m]]`` — squared-L2 convention throughout (DESIGN.md §3:
thresholds compare ``dist^2 <= tau^2`` so no sqrt is ever taken).

K-means runs fully vectorised across subspaces; centroid updates use
``segment_sum`` (no (N, M, Kc) one-hot materialisation).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.config import ProberConfig


class PQIndex(NamedTuple):
    centroids: jax.Array   # (M, Kc, ds) float32
    codes: jax.Array       # (C, M) uint8 — Kc <= 256; byte codes keep the
                           # scan cache-resident (DESIGN.md §9)
    counts: jax.Array      # (M, Kc) float32 — for incremental updates (Alg. 8)
    resid: jax.Array       # (C,) float32 — ||x - q(x)|| quantization residual
                           # (beyond-paper: enables banded ADC qualification)
    n_valid: jax.Array     # () int32 — live points; rows >= n_valid of
                           # codes/resid are capacity padding (DESIGN.md §10)
    packed: Optional[jax.Array] = None
                           # (C, M//2) uint8 — two 4-bit codes per byte
                           # (cfg.pq_pack4, Kc <= 16): halves code-matrix
                           # bandwidth in the hot loop (DESIGN.md §11).
                           # None unless built with pq_pack4.

    @property
    def m(self) -> int:
        return self.centroids.shape[0]

    @property
    def kc(self) -> int:
        return self.centroids.shape[1]

    @property
    def capacity(self) -> int:
        return self.codes.shape[0]


def split_subspaces(x: jax.Array, m: int) -> jax.Array:
    """(N, d) -> (N, M, ds)."""
    n, d = x.shape
    assert d % m == 0, f"M={m} must divide d={d}"
    return x.reshape(n, m, d // m)


ASSIGN_ROWS = 65536   # rows per assignment block: bounds the (rows, M, Kc)
                      # distance tile (128 MiB at M=32, Kc=256) — a single
                      # (N, M, Kc) tile at N=1M is 8 GiB and does not fit
                      # one 16 GB chip next to its own temporaries


def _assign_block(centroids: jax.Array, xs: jax.Array) -> jax.Array:
    # dist^2 = |x|^2 - 2 x.c + |c|^2 ; argmin over Kc
    x2 = jnp.sum(xs ** 2, axis=-1, keepdims=True)            # (n, M, 1)
    c2 = jnp.sum(centroids ** 2, axis=-1)                    # (M, Kc)
    xc = jnp.einsum("nms,mks->nmk", xs, centroids)           # (n, M, Kc)
    d2 = x2 - 2.0 * xc + c2[None]
    return jnp.argmin(d2, axis=-1).astype(jnp.int32)


def _row_blocks(a: jax.Array, rows: int, fill=0) -> jax.Array:
    """(N, ...) -> (ceil(N/rows), rows, ...), the tail padded with ``fill``."""
    pad = (-a.shape[0]) % rows
    a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                constant_values=fill)
    return a.reshape(-1, rows, *a.shape[1:])


def _block_rows(n: int) -> int:
    return max(min(n, ASSIGN_ROWS), 1)


def _subspace_blocks(xs: jax.Array, rows: int) -> jax.Array:
    """(N, M, ds) -> (blocks, rows, M·ds) row blocks of the flat vectors.

    The blocks are cut from the (N, d) view and split per block: the TPU
    pads the ds-wide minor dimension of a whole (N, M, ds) array to 128
    lanes (16 GiB at N=1M, d=128), which a block-sized view never is."""
    return _row_blocks(xs.reshape(xs.shape[0], -1), rows)


def assign(centroids: jax.Array, xs: jax.Array) -> jax.Array:
    """Nearest-centroid assignment per subspace. xs: (N, M, ds) -> (N, M).

    Rows are assigned in blocks of ``ASSIGN_ROWS`` (one block when N is
    smaller), so device memory stays bounded at any corpus size."""
    n, m, ds = xs.shape
    rows = _block_rows(n)
    codes = jax.lax.map(
        lambda b: _assign_block(centroids, b.reshape(rows, m, ds)),
        _subspace_blocks(xs, rows))
    return codes.reshape(-1, m)[:n]


def _centroid_sums(xs: jax.Array, codes: jax.Array, kc: int):
    """Per-(subspace, centroid) coordinate sums (M, Kc, ds) and member
    counts (M, Kc) of the points ``xs`` (N, M, ds) under ``codes`` (N, M),
    accumulated over the row blocks of :func:`assign` (a whole (N·M, ds)
    scatter source is lane-padded like the view :func:`_subspace_blocks`
    avoids)."""
    n, m, ds = xs.shape
    rows = _block_rows(n)
    offs = (jnp.arange(m, dtype=jnp.int32) * kc)[None, :]

    def one(acc, blk):
        xb, cb = blk
        seg = (cb + offs).reshape(-1)       # padding rows: >= m*kc, dropped
        sums = jax.ops.segment_sum(xb.reshape(rows * m, ds), seg,
                                   num_segments=m * kc)
        cnts = jax.ops.segment_sum(jnp.ones(seg.shape, jnp.float32), seg,
                                   num_segments=m * kc)
        return (acc[0] + sums, acc[1] + cnts), None

    init = (jnp.zeros((m * kc, ds), xs.dtype), jnp.zeros((m * kc,),
                                                         jnp.float32))
    (sums, cnts), _ = jax.lax.scan(
        one, init, (_subspace_blocks(xs, rows),
                    _row_blocks(codes, rows, fill=m * kc)))
    return sums.reshape(m, kc, ds), cnts.reshape(m, kc)


@partial(jax.jit, static_argnames=("cfg",))
def fit(x: jax.Array, cfg: ProberConfig, key: jax.Array) -> PQIndex:
    """Lloyd's k-means per subspace, vectorised across all M subspaces.

    Jitted as one program: run op by op, the (N, M, ds) subspace view
    would be materialised with its ds-wide minor dimension padded to 128
    lanes on a TPU — 16 GiB at N=1M, d=128."""
    m, kc = cfg.pq_m, cfg.pq_kc
    assert kc <= 256, f"Kc={kc} must fit a uint8 code"
    xs = split_subspaces(x, m)                               # (N, M, ds)
    n = xs.shape[0]
    init_rows = jax.random.choice(key, n, (kc,), replace=n < kc)
    centroids = jnp.swapaxes(xs[init_rows], 0, 1)            # (M, Kc, ds)

    def step(centroids, _):
        codes = assign(centroids, xs)                        # (N, M)
        sums, cnts = _centroid_sums(xs, codes, kc)
        new = jnp.where(cnts[..., None] > 0, sums / jnp.maximum(cnts[..., None], 1.0),
                        centroids)
        return new, None

    centroids, _ = jax.lax.scan(step, centroids, None, length=cfg.pq_iters)
    codes = assign(centroids, xs)
    _, counts = _centroid_sums(xs, codes, kc)
    resid = reconstruction_residual(centroids, codes, xs)
    codes8 = codes.astype(jnp.uint8)
    packed = None
    if cfg.pq_pack4:
        assert kc <= 16 and m % 2 == 0, \
            f"pq_pack4 needs Kc<=16 and even M, got Kc={kc}, M={m}"
        packed = pack_codes(codes8)
    return PQIndex(centroids=centroids, codes=codes8,
                   counts=counts, resid=resid,
                   n_valid=jnp.asarray(n, jnp.int32), packed=packed)


def grow(pq: PQIndex, new_capacity: int) -> PQIndex:
    """Re-pad codes/resid to a larger capacity (DESIGN.md §10). Padding rows
    are zeros — never read, because candidate ids only ever come from valid
    LSH buckets and the scan baseline masks by ``n_valid``."""
    cap = pq.codes.shape[0]
    assert new_capacity >= cap, (new_capacity, cap)
    pad = new_capacity - cap
    packed = None if pq.packed is None else \
        jnp.pad(pq.packed, ((0, pad), (0, 0)))
    return pq._replace(codes=jnp.pad(pq.codes, ((0, pad), (0, 0))),
                       resid=jnp.pad(pq.resid, ((0, pad),)),
                       packed=packed)


def pack_codes(codes: jax.Array) -> jax.Array:
    """Pack 4-bit PQ codes pairwise: (..., M) uint8 → (..., M//2) uint8.

    Byte j holds codes ``2j`` (low nibble) and ``2j+1`` (high nibble) —
    the layout :func:`unpack_codes` and the packed qualfn gathers invert.
    Requires Kc <= 16 (codes < 16) and even M.
    """
    c = codes.astype(jnp.uint8)
    return (c[..., 0::2] | (c[..., 1::2] << 4)).astype(jnp.uint8)


def unpack_codes(packed: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_codes`: (..., M//2) uint8 → (..., M) int32."""
    lo = (packed & 0xF).astype(jnp.int32)
    hi = (packed >> 4).astype(jnp.int32)
    return jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1],
                                                2 * packed.shape[-1])


def reconstruction_residual(centroids: jax.Array, codes: jax.Array,
                            xs: jax.Array) -> jax.Array:
    """||x - q(x)|| per point; xs is (N, M, ds). Row-blocked like
    :func:`assign`."""
    n, m, ds = xs.shape
    rows = _block_rows(n)

    def one(blk):
        cb, xb = blk
        recon = centroids[jnp.arange(m)[None, :], cb]     # (rows, M, ds)
        diff = xb.reshape(rows, m, ds) - recon
        return jnp.sqrt(jnp.sum(diff ** 2, axis=(-1, -2)))

    resid = jax.lax.map(one, (_row_blocks(codes, rows),
                              _subspace_blocks(xs, rows)))
    return resid.reshape(-1)[:n]


def adc_table(pq: PQIndex, q: jax.Array) -> jax.Array:
    """Alg. 4: per-query LUT ``T[m, c] = ||q_m - centroid[m,c]||^2`` (M, Kc)."""
    qs = q.reshape(pq.m, -1)                                 # (M, ds)
    diff = qs[:, None, :] - pq.centroids                     # (M, Kc, ds)
    return jnp.sum(diff ** 2, axis=-1)


class QuantLUT(NamedTuple):
    """Affine-quantized per-query ADC LUT (DESIGN.md §11).

    Entry ``(m, c)`` of the float LUT is represented as
    ``offset + scale * q8[m, c]`` with one scalar (scale, offset) per query,
    so the whole table is uint8 — 4× less VMEM/cache than float32, and the
    per-candidate accumulation is an int32 sum of M bytes. Round-to-nearest
    bounds the per-entry error by ``scale/2`` and the summed ADC error by
    ``M·scale/2``.
    """
    q8: jax.Array      # (M, Kc) uint8 (leading Q axis when batched)
    scale: jax.Array   # () float32
    offset: jax.Array  # () float32 — the LUT minimum


def quantize_lut(lut: jax.Array) -> QuantLUT:
    """Affine uint8 quantization of one (M, Kc) float LUT (Alg. 4 output).

    ``scale = (max - min) / 255`` maps the LUT range onto [0, 255];
    round-to-nearest keeps every dequantized entry within ``scale/2`` of
    the float entry (no clipping error: entries lie inside [min, max]).
    """
    lo = jnp.min(lut)
    scale = jnp.maximum((jnp.max(lut) - lo) / 255.0, 1e-20)
    q8 = jnp.clip(jnp.round((lut - lo) / scale), 0.0, 255.0).astype(jnp.uint8)
    return QuantLUT(q8=q8, scale=scale, offset=lo)


def quantized_threshold(qlut: QuantLUT, m: int, tau_sq: jax.Array) -> jax.Array:
    """Threshold for the quantized qualification test (DESIGN.md §11).

    With ``S = Σ_m q8[m, code_m]`` (int32) the dequantized ADC distance is
    ``M·offset + scale·S``, so ``dequant <= tau²  ⇔  S <= u`` with
    ``u = (tau² - M·offset) / scale``. Since S is an integer, comparing
    against ``floor(u)`` is EXACT with respect to the dequantized distances
    — the only disagreement with float32 ADC comes from the ``±M·scale/2``
    LUT rounding, so decisions match float32 exactly for every candidate
    with ``|adc² - tau²| > (M/2 + 1)·scale`` (the +1 absorbs float rounding
    of u itself; proven tight in tests/test_quantized.py).
    """
    u = (tau_sq - m * qlut.offset) / qlut.scale
    return jnp.clip(jnp.floor(u), -1.0, 255.0 * m + 1.0).astype(jnp.int32)


def build_query_lut(pq: PQIndex, q: jax.Array, cfg: ProberConfig):
    """Per-query LUT in the datapath the config asks for: float32 (Alg. 4),
    or the affine uint8 :class:`QuantLUT` when ``cfg.pq_int8_lut`` (banded
    qualification needs float distances, so it keeps the float LUT)."""
    lut = adc_table(pq, q)
    if cfg.pq_int8_lut and not cfg.pq_banded:
        return quantize_lut(lut)
    return lut


def adc_distance(lut: jax.Array, codes: jax.Array) -> jax.Array:
    """Alg. 5: squared ADC distance for codes (..., M) -> (...,).

    ``lut[m, codes[..., m]]`` summed over m — advanced indexing broadcasts
    ``arange(M)`` against the trailing code axis.
    """
    m = lut.shape[0]
    gathered = lut[jnp.arange(m), codes]   # (..., M)
    return jnp.sum(gathered, axis=-1)
