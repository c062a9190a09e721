"""PQ-ADC distance kernel (paper Alg. 5): ``dist[n] = Σ_m lut[m, codes[n,m]]``.

TPU adaptation of the LUT gather (DESIGN.md §3): TPUs have no fast random
gather, so the per-subspace lookup becomes a **compare-against-iota one-hot
contraction** executed per subspace inside the kernel — the LUT row times an
(bn, Kc) mask, accumulated over M by a static (unrolled) loop. The whole LUT
(M×Kc×4B ≤ 32 KiB for M=32, Kc=256) lives in VMEM for the kernel's lifetime;
codes stream through in (bn, M) int32 tiles.

Grid: (N/bn,).

The batched variant :func:`adc_batch` (DESIGN.md §9) serves Q concurrent
queries in a single pass over the codes: all Q per-query LUTs — (Q, M, Kc),
Q×M×Kc×4B, e.g. 2 MiB for Q=64 or 8 MiB for Q=256 at M=32/Kc=256 (size Q
to leave VMEM headroom for the code tiles) — stay resident in VMEM while
each (bn, M) code tile is read ONCE and contracted against every LUT,
emitting a (Q, bn) distance tile per grid step. The one-hot mask is shared
across queries, so the per-subspace work becomes a (Q, Kc) x (bn, Kc)^T
matmul that the MXU executes natively (the kernel gets the subspace-major
(M, Q, Kc) stack, so subspace j is a leading-dim index); code-tile
bandwidth is amortised Q-fold over the single-query kernel called in a
loop. All four entry points share one kernel body: the single-query ones
are the Q=1 case. Consumed by the batched
full-scan baseline (``core/baselines.adc_scan_estimate_batch``) — the
non-adaptive counterpart of the prober, benchmarked in
benchmarks/bench_adc.py.

Quantized datapath (DESIGN.md §11): :func:`adc_q8` / :func:`adc_batch_q8`
take the affine uint8 LUTs of ``pq.quantize_lut`` and return raw int32
entry sums ``S[n] = Σ_m qlut[m, codes[n,m]]`` (dequantize as
``offset·M + scale·S``, or compare against ``pq.quantized_threshold``
without ever leaving the integer domain). The VMEM-resident LUT block is
uint8 — 4× smaller than float32 — so 2-4× more queries' LUTs fit beside
the code tiles. Each looked-up entry (<= 255) passes exactly through the
float32 contraction and accumulates in int32, which is exact
(max sum = M·255 « 2^31).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


_NT = (((1,), (1,)), ((), ()))   # dot_general dims: A @ B.T


def _onehot(codes: jax.Array, j: int, kc: int) -> jax.Array:
    """(bn, Kc) float32 one-hot of subspace ``j``'s codes.

    ``j`` is a Python int: the lane slice of the (bn, M) code tile must be
    static for the TPU lowering, which has no dynamic lane-dim slice — so
    the subspace loops below are unrolled Python loops, not ``fori_loop``.
    """
    col = codes[:, j:j + 1]                                      # (bn, 1)
    iota = jax.lax.broadcasted_iota(jnp.int32, (codes.shape[0], kc), 1)
    return (col == iota).astype(jnp.float32)


def _lookup(lut_rows: jax.Array, onehot: jax.Array) -> jax.Array:
    """(R, Kc) LUT rows against a (bn, Kc) one-hot → (R, bn) entries.

    Every output is a single LUT entry (one nonzero term), so the f32
    contraction is exact at HIGHEST precision — which is also what keeps
    the kernels bit-identical to a gather-and-sum over the same order.
    """
    return jax.lax.dot_general(lut_rows, onehot, _NT,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _kernel(codes_ref, luts_ref, out_ref):
    codes = codes_ref[...]             # (bn, M) int32
    m, q, kc = luts_ref.shape          # subspace-major (M, Q, Kc) LUTs
    acc = jnp.zeros((q, codes.shape[0]), out_ref.dtype)
    for j in range(m):
        lut_j = luts_ref[j]                                      # (Q, Kc)
        if lut_j.dtype == jnp.uint8:   # no direct uint8 -> f32 cast on TPU;
            lut_j = lut_j.astype(jnp.int32)   # entries <= 255 stay exact
        entries = _lookup(lut_j.astype(jnp.float32), _onehot(codes, j, kc))
        acc = acc + entries.astype(out_ref.dtype)                # (Q, bn)
    out_ref[...] = acc                 # lane-dense store


def _scan(codes: jax.Array, luts: jax.Array, out_dtype, bn: int,
          interpret: bool) -> jax.Array:
    """Shared pallas_call over (N/bn,) code tiles for (Q, M, Kc) LUTs;
    the whole LUT block stays resident in VMEM. Returns (Q, N)."""
    n, m = codes.shape
    q = luts.shape[0]
    bn = min(bn, n)
    pad_n = (-n) % bn
    cp = jnp.pad(codes.astype(jnp.int32), ((0, pad_n), (0, 0)))
    luts = jnp.swapaxes(luts, 0, 1)    # (M, Q, Kc): subspace j = luts[j]
    out = pl.pallas_call(
        _kernel,
        grid=(cp.shape[0] // bn,),
        in_specs=[
            pl.BlockSpec((bn, m), lambda i: (i, 0)),
            pl.BlockSpec(luts.shape, lambda i: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((q, bn), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((q, cp.shape[0]), out_dtype),
        interpret=interpret,
    )(cp, luts)
    return out[:, :n]


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def adc(codes: jax.Array, lut: jax.Array, *, bn: int = 512,
        interpret: bool = False) -> jax.Array:
    """codes (N, M) int (any width), lut (M, Kc) f32 → squared distances (N,)."""
    return _scan(codes, lut[None], jnp.float32, bn, interpret)[0]


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def adc_batch(codes: jax.Array, luts: jax.Array, *, bn: int = 512,
              interpret: bool = False) -> jax.Array:
    """codes (N, M) int32, luts (Q, M, Kc) f32 → squared distances (Q, N).

    One scan over the codes serves all Q queries; equivalent to (but much
    cheaper than) stacking ``adc(codes, luts[i])`` for each i.
    """
    return _scan(codes, luts, jnp.float32, bn, interpret)


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def adc_q8(codes: jax.Array, qlut: jax.Array, *, bn: int = 512,
           interpret: bool = False) -> jax.Array:
    """codes (N, M) int, qlut (M, Kc) uint8 → int32 LUT-entry sums (N,).

    Integer counterpart of :func:`adc` for the quantized ADC datapath
    (DESIGN.md §11); the accumulation is exact in int32.
    """
    return _scan(codes, qlut[None], jnp.int32, bn, interpret)[0]


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def adc_batch_q8(codes: jax.Array, qluts: jax.Array, *, bn: int = 512,
                 interpret: bool = False) -> jax.Array:
    """codes (N, M) int32, qluts (Q, M, Kc) uint8 → int32 sums (Q, N).

    Integer counterpart of :func:`adc_batch` (DESIGN.md §11): one pass over
    the codes serves all Q queries with the LUT block resident in VMEM at a
    quarter of the float32 footprint — e.g. Q=256 at M=32/Kc=256 costs
    2 MiB instead of 8 MiB, so 2-4× more queries batch into one scan before
    VMEM pressure forces a split.
    """
    return _scan(codes, qluts, jnp.int32, bn, interpret)
