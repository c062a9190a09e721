"""Neighboring-based adaptive bucket probing (paper §4.3/4.4, Alg. 1–3).

TPU-native formulation (DESIGN.md §3): rings N_k are masks over the unique
bucket codes (``hamming == k``); ring candidates are gathered into a static
``ring_budget`` buffer via a cumsum/searchsorted inversion of the sorted-CSR
layout; progressive sampling walks a random permutation of that buffer in
fixed-size chunks inside ``lax.while_loop``, checking the Chernoff bounds of
§4.5 at the doubling schedule points ``s_{i+1} = 2 s_i``.

Everything is shape-static, jit-able and vmap-able over queries.
:func:`estimate` handles one query; :func:`estimate_batch` (DESIGN.md §9)
is the first-class multi-query path — the LSH hash of all Q queries is one
matmul, ring construction and progressive sampling are vmapped over queries
(each query keeps its own Chernoff stopping state inside the shared
``while_loop``), and the per-query PQ LUTs arrive pre-built as (Q, M, Kc)
(or as a batched :class:`~repro.core.pq.QuantLUT` on the quantized ADC
datapath, DESIGN.md §11).

Skew resilience (DESIGN.md §11): with ``cfg.lane_block > 0`` (the default)
the batched path flattens the (Q, L) lane grid and periodically compacts
the still-active lanes into a dense prefix, so a few slow (query, table)
lanes no longer keep every finished lane's slab work alive — wall-clock
moves from max-lane toward mean-lane cost under skewed (tau, query) mixes
while staying bit-identical to the monolithic schedule.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import lsh, pq as pqmod, sampling
from repro.core.config import ProberConfig

# qualfn(ids: (c,) int32) -> qualification weight in [0,1] per point
# (exact: 1[d^2 <= tau^2]; banded ADC: interpolated within the residual band)
QualFn = Callable[[jax.Array], jax.Array]


class TableView(NamedTuple):
    """One hash table's slice of the index (leading L axis stripped).

    Capacity padding (DESIGN.md §10) needs no extra plumbing here: dead
    point rows live in the sentinel bucket at row ``n_buckets``, and every
    ring op below masks the bucket axis by ``n_buckets`` (via
    ``hamming_to_buckets``'s K+1 distance), so rings, gathers and the
    central count only ever see live points.
    """
    order: jax.Array          # (N,)
    bucket_codes: jax.Array   # (B, K)
    bucket_starts: jax.Array  # (B,)
    bucket_sizes: jax.Array   # (B,)
    n_buckets: jax.Array      # ()


def table_views(index: lsh.LSHIndex) -> TableView:
    """Stacked (L, ...) view suitable for vmap over tables."""
    return TableView(index.order, index.bucket_codes, index.bucket_starts,
                     index.bucket_sizes, index.n_buckets)


def gather_ring_from_cum(view: TableView, cum: jax.Array, budget: int):
    """Gather up to ``budget`` point ids given a ring's size cumsum ``cum``.

    ``cum`` is ``cumsum(where(ring_mask, bucket_sizes, 0))`` — precomputed so
    the batched path can build every ring's cumsum in ONE op (DESIGN.md §9).
    Returns (ids (budget,), valid (budget,), total ()) where ``total`` is the
    *full* ring population |N_k| (may exceed budget).
    """
    total = cum[-1]
    slots = jnp.arange(budget, dtype=jnp.int32)
    j = jnp.searchsorted(cum, slots, side="right").astype(jnp.int32)
    j = jnp.minimum(j, cum.shape[0] - 1)
    prev = jnp.where(j > 0, cum[jnp.maximum(j - 1, 0)], 0)
    pos = view.bucket_starts[j] + (slots - prev)
    valid = slots < total
    pos = jnp.clip(jnp.where(valid, pos, 0), 0, view.order.shape[0] - 1)
    return view.order[pos], valid, total


def gather_ring(view: TableView, ring_mask: jax.Array, budget: int):
    """Gather up to ``budget`` point ids belonging to masked buckets."""
    sizes = jnp.where(ring_mask, view.bucket_sizes, 0)
    return gather_ring_from_cum(view, jnp.cumsum(sizes), budget)


def ring_cumsums(view: TableView, ham: jax.Array, n_rings: int) -> jax.Array:
    """Masked size cumsums for rings k = 0..n_rings in ONE batched op.

    Returns (n_rings+1, B); row k is ``cumsum(where(ham == k, sizes, 0))``,
    bit-identical to what :func:`gather_ring` would compute per ring — but
    hoisted out of the adaptive probing loop, where a fresh (B,) cumsum per
    visited ring dominated the profile (DESIGN.md §9).
    """
    ks = jnp.arange(n_rings + 1, dtype=jnp.int32)
    masks = ham[None, :] == ks[:, None]                      # (R, B)
    return jnp.cumsum(jnp.where(masks, view.bucket_sizes[None, :], 0), axis=-1)


def _prp_eval(idx: jax.Array, rks: jax.Array, mask: jax.Array,
              n_bits) -> jax.Array:
    """Keyed multiply/xorshift PRP on Z_{2^n}; ``mask = 2^n - 1``.

    Each round composes three bijections on Z_{2^n} (odd-multiplier product,
    xor with a right shift, keyed add), so the map is an exact permutation
    of [0, 2^n). ``n_bits``/``mask`` may be traced values — the progressive
    sampler evaluates the PRP over a per-ring power-of-two domain chosen at
    run time (DESIGN.md §9). Mixing is pseudo-random rather than uniformly
    distributed over S_n; accuracy envelopes are validated in
    tests/test_prober.py and benchmarks/bench_qerror.py.
    """
    x = idx.astype(jnp.uint32)
    mask = mask.astype(jnp.uint32) if hasattr(mask, "astype") else \
        jnp.uint32(mask)
    for i in range(3):
        x = (x * (rks[2 * i] | jnp.uint32(1))) & mask
        shift = n_bits // 2 + (i % 2) + 1
        x = x ^ jnp.right_shift(x, jnp.asarray(shift, jnp.uint32))
        x = (x + rks[2 * i + 1]) & mask
    return x.astype(jnp.int32)


@jax.named_scope("probe/central")
def _count_central(view: TableView, cum0: jax.Array, qualfn: QualFn,
                   cfg: ProberConfig):
    """Alg. 3: exact brute-force count inside B_central.

    If the bucket exceeds ``central_budget`` the exact count over the gathered
    prefix is scaled by ``total/seen`` (static-shape cap; DESIGN.md §3).
    """
    ids, valid, total = gather_ring_from_cum(view, cum0, cfg.central_budget)
    qualified = jnp.sum(qualfn(ids) * valid)
    seen = jnp.sum(valid)
    scale = jnp.where(seen > 0, total / jnp.maximum(seen, 1), 0.0)
    return qualified * scale, seen


class LaneCtx(NamedTuple):
    """Per-(query, table) loop constants of the progressive sampler.

    Built once per lane by :func:`_table_setup` (ring construction, Alg. 2's
    schedule anchors) and read-only inside the slab loop — which is what
    lets the compacting scheduler (DESIGN.md §11) gather just the active
    lanes' rows per tile instead of carrying them through the loop state.
    """
    cums: jax.Array            # (K+1, B) ring size cumsums (row k = ring k)
    rks: jax.Array             # (6,) PRP round keys (Alg. 2)
    prings: jax.Array          # (K,) per-ring PRP domain P_k = next_pow2(cap)
    caps: jax.Array            # (K,) per-ring sample caps min(|N_k|, budget)
    nbits: jax.Array           # (K,) log2(P_k)
    totals_f: jax.Array        # (K,) |N_k| (local shard counts)
    w_caps: jax.Array          # (K,) schedule cap ceil(s_max |N_k|)
    first_targets: jax.Array   # (K,) first anchor ceil(s1 |N_k|)
    visit_budget: jax.Array    # () int32 (scaled by shards in pooled mode)


def _table_setup(view: TableView, qcode: jax.Array, central_qualfn: QualFn,
                 cfg: ProberConfig, key: jax.Array):
    """Loop-free ring construction for one (query, table) lane (DESIGN.md
    §9): the batched Hamming compare, ONE cumsum covering every ring, the
    exact central count (Alg. 3) and the per-ring PRP domains / Chernoff
    schedule anchors. Returns ``(ctx, est0, visited0)``. Its ops carry the
    device scopes ``probe/rings`` and ``probe/central``."""
    with jax.named_scope("probe/rings"):
        ham = lsh.hamming_to_buckets(view.bucket_codes, view.n_buckets, qcode)
        n_rings = view.bucket_codes.shape[-1]
        cums = ring_cumsums(view, ham, n_rings)                # (K+1, B)
        rks = jax.random.bits(key, (6,), jnp.uint32)   # PRP round keys, Alg. 2
        totals = cums[1:, -1]                                  # (K,) |N_k|
        totals_f = totals.astype(jnp.float32)
        caps = jnp.minimum(totals, cfg.ring_budget)
        # per-ring PRP domain: P_k = 2^{nbits_k} = next_pow2(cap_k)
        nbits = jnp.where(caps <= 1, 0,
                          32 - jax.lax.clz(jnp.maximum(caps - 1, 1)))
        prings = jnp.left_shift(1, nbits)                      # (K,)
        # schedule anchors per ring (Alg. 2 line 8): w_1 = ceil(s1 * |N_k|)
        w_caps = jnp.minimum(jnp.ceil(cfg.s_max * totals_f),
                             caps.astype(jnp.float32))
        first_targets = jnp.maximum(jnp.ceil(cfg.s1 * totals_f), 1.0)
    est0, visited0 = _count_central(view, cums[0], central_qualfn, cfg)
    ctx = LaneCtx(cums=cums, rks=rks, prings=prings, caps=caps, nbits=nbits,
                  totals_f=totals_f, w_caps=w_caps,
                  first_targets=first_targets,
                  visit_budget=jnp.int32(cfg.max_visit))
    return ctx, est0, visited0


def _init_state(ctx: LaneCtx, est0, visited0, n_rings: int):
    return {"k": jnp.int32(1), "ci": jnp.int32(0), "w": jnp.int32(0),
            "wq": jnp.float32(0.0), "target": ctx.first_targets[0],
            "est": est0, "nvisited": visited0, "ptf": jnp.bool_(False),
            "done": jnp.bool_(n_rings < 1) | (visited0 >= ctx.visit_budget)}


def _make_ring_fn(qualfn: QualFn, exact_qualfn: QualFn | None,
                  cfg: ProberConfig):
    """Ring-indexed qualification dispatch shared by both schedulers: near
    rings k <= ``pq_exact_rings`` carry the selectivity mass (paper Fig. 1),
    so they may route through exact distances while farther rings use ADC
    (beyond-paper accuracy fix)."""
    if exact_qualfn is not None and cfg.pq_exact_rings > 0:
        return lambda k, ids: jax.lax.cond(
            k <= cfg.pq_exact_rings, exact_qualfn, qualfn, ids)
    return lambda k, ids: qualfn(ids)


@jax.named_scope("probe/slab")
def _slab_step(s, ctx: LaneCtx, get_cum, get_starts, get_order, ring_fn,
               cfg: ProberConfig, n_buckets: int, n_points: int,
               n_rings: int, axis_name=None):
    """One progressive-sampling slab (Alg. 2 body) for one lane.

    THE shared hot-loop body: the monolithic ``while_loop`` of
    :func:`estimate_one_table` and the compacting tiled scheduler of
    :func:`_estimate_batch_compact` both run exactly this function, which is
    what makes the two schedules bit-identical per lane (tested in
    tests/test_compact.py). ``get_cum``/``get_starts``/``get_order``
    abstract the index lookups (closure over one table's view vs. a
    lane-indexed gather into the stacked (L, ...) arrays); ``ring_fn(k,
    ids)`` is the per-ring qualification from :func:`_make_ring_fn`.

    Visit-budget check: the in-progress ring's (pooled) sample count ``wf``
    is folded into the budget test EVERY slab — ``nvisited`` alone only
    advances at ring completion, so checking it by itself could not fire
    mid-ring and overshot ``max_visit`` by up to a whole ring (bugfix, this
    PR). A budget hit forces ring completion, so the partial ring's
    (unbiased) estimate is still folded into the total. Its ops carry the
    device scope ``probe/slab``.
    """
    chunk = cfg.chunk
    slot_iota = jnp.arange(chunk, dtype=jnp.int32)
    k, ci, row = s["k"], s["ci"], s["k"] - 1
    p_ring = ctx.prings[row]
    idx = ci * chunk + slot_iota
    p_slab = _prp_eval(idx, ctx.rks, p_ring - 1, ctx.nbits[row])
    cum = get_cum(k)                                           # (B,)
    ok = (idx < p_ring) & (p_slab < ctx.caps[row])
    # resolve slab -> point ids through the ring's CSR cumsum
    j = jnp.minimum(jnp.searchsorted(cum, p_slab, side="right")
                    .astype(jnp.int32), n_buckets - 1)
    prev = jnp.where(j > 0, cum[jnp.maximum(j - 1, 0)], 0)
    pos = get_starts(j) + (p_slab - prev)
    pos = jnp.clip(jnp.where(ok, pos, 0), 0, n_points - 1)
    sl = get_order(pos)
    wq = s["wq"] + jnp.sum(ring_fn(k, sl) * ok)
    w = s["w"] + jnp.sum(ok)
    exhausted = (ci + 1) * chunk >= p_ring     # local PRP domain walked
    # per-shard unbiased ring estimate |N_k|·p̂ (== the pooled one when
    # axis_name is None)
    ring_est = ctx.totals_f[row] * wq / jnp.maximum(w.astype(jnp.float32),
                                                    1.0)
    if axis_name is None:
        wf, wq_pool, all_exhausted = w.astype(jnp.float32), wq, exhausted
    else:
        # ONE small psum pools this slab's (w, w') Chernoff statistics,
        # the exhaustion vote and the weighted ring estimate; every
        # stopping quantity below derives from it, so the loop stays in
        # lockstep across shards
        pooled = jax.lax.psum(
            jnp.stack([w.astype(jnp.float32), wq,
                       exhausted.astype(jnp.float32), jnp.float32(1.0),
                       ring_est]),
            axis_name)
        wf, wq_pool = pooled[0], pooled[1]
        all_exhausted = pooled[2] >= pooled[3]
        ring_est = pooled[4]
    p_hat = wq_pool / jnp.maximum(wf, 1.0)
    w_cap = ctx.w_caps[row]
    at_schedule = (wf >= s["target"]) | (wf >= w_cap)
    if not cfg.schedule_checks:      # static: check bounds every chunk
        at_schedule = jnp.bool_(True)
    cond1 = sampling.stop_sampling(p_hat, wf, cfg.a_const, cfg.eps)
    cond2 = sampling.stop_probing(p_hat, wf, cfg.a_const, cfg.eps)
    budget_hit = (s["nvisited"] + wf.astype(jnp.int32)) >= ctx.visit_budget
    ring_done = (at_schedule & (cond1 | cond2)) | (wf >= w_cap) | \
        all_exhausted | budget_hit
    ptf = s["ptf"] | (at_schedule & cond2)
    target = jnp.where(at_schedule, s["target"] * 2.0, s["target"])
    est = jnp.where(ring_done, s["est"] + ring_est, s["est"])
    nvisited = jnp.where(ring_done, s["nvisited"] + wf.astype(jnp.int32),
                         s["nvisited"])
    nk = jnp.where(ring_done, k + 1, k)
    nrow = jnp.minimum(nk - 1, n_rings - 1)
    return {
        "k": nk, "ci": jnp.where(ring_done, 0, ci + 1),
        "w": jnp.where(ring_done, 0, w),
        "wq": jnp.where(ring_done, 0.0, wq),
        "target": jnp.where(ring_done, ctx.first_targets[nrow], target),
        "est": est, "nvisited": nvisited, "ptf": ptf,
        "done": (nk > n_rings) | ptf | budget_hit,
    }


def estimate_one_table(view: TableView, qcode: jax.Array, qualfn: QualFn,
                       cfg: ProberConfig, key: jax.Array,
                       central_qualfn: QualFn | None = None,
                       exact_qualfn: QualFn | None = None,
                       axis_name=None):
    """Alg. 1: central bucket exactly, then rings k = 1..K adaptively.

    ``axis_name`` switches on the distributed *pooled-stopping* ("sync")
    mode (DESIGN.md §4): inside a shard_map over that mesh axis, the
    per-slab (w, w') Chernoff statistics are pooled with ONE small psum per
    ``while_loop`` iteration, so the ε-test of §4.5 sees the GLOBAL
    selectivity instead of each shard's local one. Every control decision
    (schedule anchors, ring advance, PTF, termination) is derived from the
    pooled values only, so all shards run the loop in lockstep — which is
    also what makes the in-loop collective legal. The returned estimate is
    the global one, identical (replicated) on every shard; ``nvisited``
    counts globally pooled samples, so the visit budget is scaled to
    ``cfg.max_visit`` × shards — max_visit keeps its per-shard meaning and
    the mesh spends the same total budget in both stopping modes.

    ``central_qualfn`` lets f_central stay exact (Alg. 3 is brute force —
    the paper applies ADC only inside f_neighbor) while rings use ADC;
    ``exact_qualfn`` independently routes near rings (k <= pq_exact_rings)
    through exact distances, so the pq_exact_central and pq_exact_rings
    knobs compose without coupling.

    Restructured for batching (DESIGN.md §9) into two phases:

    * **Ring construction** (loop-free): all rings' size cumsums come from
      ONE batched cumsum over the (trimmed) bucket axis; one shared
      pseudo-random permutation ``pi`` of the ring budget covers every ring.
      Nothing per-ring is materialised — so under a query batch this phase
      is a handful of fused, lockstep-free vector ops.
    * **Progressive sampling** (ONE flat ``while_loop``): each iteration
      evaluates one ``chunk``-sized slab of a keyed PRP over the current
      ring's own power-of-two domain P_k = next_pow2(cap_k), rejection-masks
      entries ``>= cap_k`` (the surviving subsequence of a permutation is a
      uniform random permutation of the ring's candidates, and P_k < 2 cap_k
      bounds the rejection rate below 1/2), resolves the slab's candidate
      ids through the ring cumsum on the fly, and carries a per-lane cursor
      ``(k, ci)`` plus the per-ring Chernoff state (Alg. 2) — folding the
      ring estimate and advancing ``k`` when the ring's stopping rule fires.
      Under vmap, total iterations = max over queries of the slabs that
      query actually needs — not (max rings) x (max chunks per ring), which
      is what the previous nested while_loops cost a batch — and each
      iteration is exactly the op-overhead-dominated work that batching
      amortises.
    """
    final = _run_one_table(view, qcode, qualfn, cfg, key,
                           central_qualfn=central_qualfn,
                           exact_qualfn=exact_qualfn, axis_name=axis_name)
    return final["est"], final["nvisited"]


def _run_one_table(view: TableView, qcode: jax.Array, qualfn: QualFn,
                   cfg: ProberConfig, key: jax.Array,
                   central_qualfn: QualFn | None = None,
                   exact_qualfn: QualFn | None = None,
                   axis_name=None) -> dict:
    """The :func:`estimate_one_table` body, returning the loop's FINAL state
    dict instead of just (est, nvisited) — ``final["k"] - 1`` is the deepest
    ring the probe folded, which the estimate cache snapshots for its epoch
    invalidation check (DESIGN.md §12)."""
    n_rings = view.bucket_codes.shape[-1]  # max k = number of hash functions
    n_buckets = view.bucket_sizes.shape[-1]
    ctx, est0, visited0 = _table_setup(view, qcode, central_qualfn or qualfn,
                                       cfg, key)
    if axis_name is not None:
        # pooled-stopping mode: the central count, schedule anchors and
        # sample caps become GLOBAL, so every stopping decision below is
        # shard-invariant (the PRP domains/caps above stay local — each
        # shard still samples only its own candidates). ``totals_f`` itself
        # stays LOCAL: each shard's ring estimate |N_k,s|·p̂_s is unbiased
        # under its own uniform sampling, and the psum of those is the
        # global ring count — pooling p̂ instead would overweight shards
        # that sample a larger fraction of their ring.
        est0 = jax.lax.psum(est0, axis_name)
        visited0 = jax.lax.psum(visited0, axis_name)
        totals_sched = jax.lax.psum(ctx.totals_f, axis_name)
        # nvisited pools globally here, so scale the visit budget by the
        # axis size — cfg.max_visit keeps its per-shard meaning and the
        # mesh gets the same total budget in both stopping modes
        ctx = ctx._replace(
            w_caps=jax.lax.psum(ctx.w_caps, axis_name),
            first_targets=jnp.maximum(jnp.ceil(cfg.s1 * totals_sched), 1.0),
            visit_budget=ctx.visit_budget *
            jax.lax.psum(jnp.int32(1), axis_name))

    ring_fn = _make_ring_fn(qualfn, exact_qualfn, cfg)

    def body(s):
        return _slab_step(s, ctx, lambda k: ctx.cums[k],
                          lambda j: view.bucket_starts[j],
                          lambda pos: view.order[pos], ring_fn, cfg,
                          n_buckets, view.order.shape[0], n_rings,
                          axis_name=axis_name)

    init = _init_state(ctx, est0, visited0, n_rings)
    with jax.named_scope("probe/slab"):     # the loop's own control too
        return jax.lax.while_loop(lambda s: ~s["done"], body, init)


def make_exact_qualfn(x: jax.Array, q: jax.Array, tau_sq: jax.Array,
                      use_kernels: bool = False) -> QualFn:
    """Exact squared-L2 qualification (Def. 3): 1[d^2 <= tau^2]. The
    corpus may be stored wider than ``q`` (``estimator.stored_width``):
    ``q`` gets the same zero columns."""
    q = jnp.pad(q, (0, x.shape[-1] - q.shape[-1]))

    def fn(ids: jax.Array) -> jax.Array:
        rows = x[ids]                       # (c, d)
        if use_kernels:
            from repro.kernels import ops
            d2 = ops.l2dist(rows, q[None, :])[:, 0]
        else:
            diff = rows - q[None, :]
            d2 = jnp.sum(diff * diff, axis=-1)
        return (d2 <= tau_sq).astype(jnp.float32)
    return fn


def _gather_codes(codes: jax.Array, packed: jax.Array | None,
                  ids: jax.Array) -> jax.Array:
    """Candidate code rows for ``ids`` — through the packed 4-bit matrix
    when available (half the gather bandwidth, DESIGN.md §11), else the
    byte codes. Both return identical integer code values."""
    if packed is not None:
        return pqmod.unpack_codes(packed[ids])
    return codes[ids]


def make_adc_qualfn(codes: jax.Array, lut: jax.Array, tau_sq: jax.Array,
                    resid: jax.Array | None = None,
                    banded: bool = False, use_kernels: bool = False,
                    packed: jax.Array | None = None) -> QualFn:
    """PQ-ADC qualification via the per-query LUT (Alg. 5).

    ``banded=False`` is the paper-faithful hard threshold on the ADC distance.
    ``banded=True`` (beyond-paper, DESIGN.md §3) uses the stored quantization
    residual r = ||p - q(p)||: by the triangle inequality the true distance
    lies in [max(0, adc - r), adc + r]; qualification weight is the fraction
    of that band below tau (linear CDF surrogate) — removes the systematic
    over/under-count when quantization distortion is comparable to tau.
    """
    m = lut.shape[0]
    marange = jnp.arange(m)
    tau = jnp.sqrt(tau_sq)

    def fn(ids: jax.Array) -> jax.Array:
        c = _gather_codes(codes, packed, ids)                  # (c, M)
        if use_kernels:
            from repro.kernels import ops
            adc_sq = ops.adc(c, lut)
        else:
            adc_sq = jnp.sum(lut[marange, c], axis=-1)
        if not banded or resid is None:
            return (adc_sq <= tau_sq).astype(jnp.float32)
        adc = jnp.sqrt(jnp.maximum(adc_sq, 0.0))
        r = resid[ids]
        lo = jnp.maximum(adc - r, 0.0)
        hi = adc + r
        w = jnp.where(hi > lo, (tau - lo) / jnp.maximum(hi - lo, 1e-12),
                      (adc <= tau).astype(jnp.float32))
        return jnp.clip(w, 0.0, 1.0)
    return fn


def make_adc_qualfn_q8(codes: jax.Array, qlut: "pqmod.QuantLUT",
                       tau_sq: jax.Array, use_kernels: bool = False,
                       packed: jax.Array | None = None) -> QualFn:
    """Quantized-domain ADC qualification (DESIGN.md §11).

    The per-candidate distance never leaves the integer domain: gather M
    uint8 LUT entries, accumulate in int32, and compare against
    ``pq.quantized_threshold`` — exact w.r.t. the dequantized distances, so
    the decision agrees with float32 ADC for every candidate whose float
    distance is farther than ``(M/2 + 1)·scale`` from ``tau²`` (the LUT
    rounding band; tests/test_quantized.py). The hot loop touches a
    uint8 LUT (4× smaller than float32) and — with ``packed`` — a 4-bit
    code matrix, which is the bandwidth the slab gathers are bound by.
    """
    m = qlut.q8.shape[0]
    marange = jnp.arange(m)
    thresh = pqmod.quantized_threshold(qlut, m, tau_sq)

    def fn(ids: jax.Array) -> jax.Array:
        c = _gather_codes(codes, packed, ids)                  # (c, M)
        if use_kernels:
            from repro.kernels import ops
            s = ops.adc_q8(c, qlut.q8)
        else:
            s = jnp.sum(qlut.q8[marange, c].astype(jnp.int32), axis=-1)
        return (s <= thresh).astype(jnp.float32)
    return fn


def _make_qualfns(x: jax.Array, q: jax.Array, tau_sq: jax.Array,
                  cfg: ProberConfig, pq_codes, pq_lut, pq_resid,
                  pq_packed=None):
    """Qualification routing shared by :func:`estimate` and
    :func:`estimate_batch` (keeping the two paths bit-identical).

    Returns (qualfn, central_qualfn, exact_qualfn): the ring distance
    function, the exact function for B_central (None = use ``qualfn``,
    the ``pq_exact_central=False`` serving trade), and the exact function
    for near rings k <= ``pq_exact_rings`` (None = ADC everywhere).
    ``pq_lut`` may be a float (M, Kc) table or a
    :class:`~repro.core.pq.QuantLUT` — the latter routes rings through the
    quantized integer datapath (DESIGN.md §11).
    """
    if pq_codes is not None and pq_lut is not None:
        if isinstance(pq_lut, pqmod.QuantLUT):
            qualfn = make_adc_qualfn_q8(pq_codes, pq_lut, tau_sq,
                                        use_kernels=cfg.use_kernels,
                                        packed=pq_packed)
        else:
            qualfn = make_adc_qualfn(pq_codes, pq_lut, tau_sq, resid=pq_resid,
                                     banded=cfg.pq_banded,
                                     use_kernels=cfg.use_kernels,
                                     packed=pq_packed)
        exact = make_exact_qualfn(x, q, tau_sq, use_kernels=cfg.use_kernels) \
            if (cfg.pq_exact_central or cfg.pq_exact_rings > 0) else None
        return (qualfn,
                exact if cfg.pq_exact_central else None,   # Alg. 3
                exact if cfg.pq_exact_rings > 0 else None)
    return (make_exact_qualfn(x, q, tau_sq, use_kernels=cfg.use_kernels),
            None, None)


@partial(jax.jit, static_argnames=("cfg",))
def estimate(index: lsh.LSHIndex, x: jax.Array, q: jax.Array, tau: jax.Array,
             cfg: ProberConfig, key: jax.Array,
             pq_codes: jax.Array | None = None,
             pq_lut: jax.Array | None = None,
             pq_resid: jax.Array | None = None,
             pq_packed: jax.Array | None = None) -> jax.Array:
    """Estimate |{p : ||p - q|| <= tau}| for one query. Averages the
    per-table estimates over the L tables (each is unbiased for the full
    cardinality since every point lives in exactly one ring per table)."""
    tau_sq = jnp.asarray(tau, jnp.float32) ** 2
    qcodes = lsh.hash_point(index.params, q, index.n_tables)   # (L, K)
    views = table_views(index)
    qualfn, central_qualfn, exact_qualfn = _make_qualfns(
        x, q, tau_sq, cfg, pq_codes, pq_lut, pq_resid, pq_packed=pq_packed)
    keys = jax.random.split(key, index.n_tables)

    def per_table(view, qcode, k):
        est, _ = estimate_one_table(view, qcode, qualfn, cfg, k,
                                    central_qualfn=central_qualfn,
                                    exact_qualfn=exact_qualfn)
        return est

    ests = jax.vmap(per_table)(views, qcodes, keys)
    return jnp.mean(ests)


def _estimate_batch_compact(index: lsh.LSHIndex, x: jax.Array, qs: jax.Array,
                            taus: jax.Array, cfg: ProberConfig,
                            keys: jax.Array, pq_codes=None, pq_luts=None,
                            pq_resid=None, pq_packed=None,
                            with_stats: bool = False):
    """Skew-resilient batched scheduler (DESIGN.md §11).

    The (Q, L) lane grid is flattened into one lane axis. Ring construction
    runs vmapped exactly like the monolithic path; the progressive-sampling
    loop is then driven by a compacting outer ``while_loop``:

    1. **Compact**: argsort the lane ``done`` mask (composed with the lane
       position for a deterministic, stability-independent order) so every
       still-active lane occupies a dense prefix; permute the small per-lane
       loop state alongside a lane-id permutation.
    2. **Tile**: run ``ceil(n_active / lane_tile)``-many fixed-size tiles —
       each gathers its lanes' :class:`LaneCtx` rows and runs
       ``cfg.lane_block`` slab iterations of the SAME :func:`_slab_step`
       body the monolithic loop uses (lanes finishing mid-block freeze via
       the same select masking `vmap`-of-`while_loop` applies).

    Finished lanes beyond the active prefix cost nothing, so total slab work
    tracks the SUM of per-lane slab counts (mean-lane) instead of
    ``n_lanes ×`` the slowest lane (max-lane) — the win under skewed
    (tau, query) mixes. Per-lane slab sequences, PRNG keys and reduction
    shapes are unchanged, so results are bit-identical to the monolithic
    schedule for every (lane_block, lane_tile) (tests/test_compact.py).

    Local-control only: every compaction decision derives from this
    process's own ``done`` flags, so the pooled-stopping ``sync`` mode
    (in-loop psum, DESIGN.md §4) keeps the monolithic lockstep loop —
    :func:`estimate_batch` routes ``axis_name`` calls there.
    """
    with jax.named_scope("probe/prep"):
        qcodes = lsh.hash_point(index.params, qs, index.n_tables)  # (Q, L, K)
    views = table_views(index)
    use_pq = pq_codes is not None and pq_luts is not None
    nq = qs.shape[0]
    nt = index.n_tables
    n_rings = views.bucket_codes.shape[-1]
    n_buckets = views.bucket_sizes.shape[-1]
    n_points = views.order.shape[-1]
    tau_sqs = jnp.asarray(taus, jnp.float32) ** 2

    # ---- per-lane ring construction (vmapped, loop-free; DESIGN.md §9) ----
    def setup_q(q, tau_sq, qcode_q, key, lut):
        qualfn, central_qualfn, _ = _make_qualfns(
            x, q, tau_sq, cfg, pq_codes if use_pq else None, lut, pq_resid,
            pq_packed=pq_packed)
        tkeys = jax.random.split(key, nt)
        return jax.vmap(
            lambda view, qc, k: _table_setup(view, qc,
                                             central_qualfn or qualfn,
                                             cfg, k)
        )(views, qcode_q, tkeys)

    if use_pq:
        ctx, est0, visited0 = jax.vmap(setup_q)(qs, tau_sqs, qcodes, keys,
                                                pq_luts)
    else:
        ctx, est0, visited0 = jax.vmap(
            lambda q, t, qc, k: setup_q(q, t, qc, k, None)
        )(qs, tau_sqs, qcodes, keys)

    # ---- flatten (Q, L) -> lanes, pad to a multiple of the tile size ----
    nl = nq * nt
    tile = max(min(cfg.lane_tile, nl), 1)
    nlp = -(-nl // tile) * tile

    def flat(a):
        a = a.reshape((nl,) + a.shape[2:])
        if nlp > nl:   # padding lanes replicate lane 0 (valid indices, done)
            a = jnp.concatenate(
                [a, jnp.broadcast_to(a[:1], (nlp - nl,) + a.shape[1:])],
                axis=0)
        return a

    ctx = jax.tree_util.tree_map(flat, ctx)
    est0, visited0 = flat(est0), flat(visited0)
    lane_q = flat(jnp.broadcast_to(
        jnp.arange(nq, dtype=jnp.int32)[:, None], (nq, nt)))
    lane_t = flat(jnp.broadcast_to(
        jnp.arange(nt, dtype=jnp.int32)[None, :], (nq, nt)))
    pad_lane = jnp.arange(nlp) >= nl
    state = {"k": jnp.full((nlp,), 1, jnp.int32),
             "ci": jnp.zeros((nlp,), jnp.int32),
             "w": jnp.zeros((nlp,), jnp.int32),
             "wq": jnp.zeros((nlp,), jnp.float32),
             "target": ctx.first_targets[:, 0],
             "est": est0, "nvisited": visited0,
             "ptf": jnp.zeros((nlp,), bool),
             "done": jnp.bool_(n_rings < 1) |
             (visited0 >= ctx.visit_budget) | pad_lane}

    # LaneCtx rows are gathered per tile; the (K+1, B) cumsums stay out of
    # the tile gather — each slab fetches only its lane's CURRENT ring row
    cums_all = ctx.cums
    small_ctx = ctx._replace(cums=None)
    block = max(cfg.lane_block, 1)

    def lane_step(s, lane, lctx, tid, q, tau_sq, lut):
        qualfn, _, exact_qualfn = _make_qualfns(
            x, q, tau_sq, cfg, pq_codes if use_pq else None, lut, pq_resid,
            pq_packed=pq_packed)
        ring_fn = _make_ring_fn(qualfn, exact_qualfn, cfg)
        return _slab_step(s, lctx, lambda k: cums_all[lane, k],
                          lambda j: views.bucket_starts[tid, j],
                          lambda pos: views.order[tid, pos], ring_fn, cfg,
                          n_buckets, n_points, n_rings)

    vstep = jax.vmap(lane_step)

    def outer_cond(c):
        return jnp.any(~c[1]["done"])

    def outer_body(c):
        perm0, st = c
        # deterministic compaction order: unique keys (done, position) make
        # the argsort independent of sort stability
        key_order = jnp.argsort(st["done"].astype(jnp.int32) * nlp +
                                jnp.arange(nlp, dtype=jnp.int32))
        perm = perm0[key_order]
        st = {kk: v[key_order] for kk, v in st.items()}
        n_active = jnp.sum(~st["done"]).astype(jnp.int32)
        n_tiles = (n_active + tile - 1) // tile

        def tile_work(t, stt):
            sl = t * tile
            s_t = {kk: jax.lax.dynamic_slice_in_dim(v, sl, tile)
                   for kk, v in stt.items()}
            lanes = jax.lax.dynamic_slice_in_dim(perm, sl, tile)
            lctx_t = jax.tree_util.tree_map(lambda a: a[lanes], small_ctx)
            qi, ti = lane_q[lanes], lane_t[lanes]
            q_t, tau_t = qs[qi], tau_sqs[qi]
            lut_t = jax.tree_util.tree_map(lambda a: a[qi], pq_luts) \
                if use_pq else None

            def one_slab(_, s_c):
                new = vstep(s_c, lanes, lctx_t, ti, q_t, tau_t, lut_t)
                return {kk: jnp.where(s_c["done"], s_c[kk], new[kk])
                        for kk in s_c}

            s_t = jax.lax.fori_loop(0, block, one_slab, s_t)
            return {kk: jax.lax.dynamic_update_slice_in_dim(
                stt[kk], s_t[kk], sl, 0) for kk in stt}

        st = jax.lax.fori_loop(0, n_tiles, tile_work, st)
        return (perm, st)

    perm, st = jax.lax.while_loop(outer_cond, outer_body,
                                  (jnp.arange(nlp, dtype=jnp.int32), state))

    def unperm(v, dtype):
        return jnp.zeros((nlp,), dtype).at[perm].set(v)[:nl].reshape(nq, nt)

    ests = unperm(st["est"], jnp.float32).mean(axis=1)
    if not with_stats:
        return ests
    probed_k = jnp.clip(unperm(st["k"], jnp.int32) - 1, 0, n_rings)
    nvis = unperm(st["nvisited"], jnp.int32).sum(axis=1)
    return ests, probed_k, nvis


@partial(jax.jit, static_argnames=("cfg", "axis_name", "with_stats"))
def estimate_batch(index: lsh.LSHIndex, x: jax.Array, qs: jax.Array,
                   taus: jax.Array, cfg: ProberConfig, keys: jax.Array,
                   pq_codes: jax.Array | None = None,
                   pq_luts: jax.Array | None = None,
                   pq_resid: jax.Array | None = None,
                   pq_packed: jax.Array | None = None,
                   axis_name=None, with_stats: bool = False):
    """Batched Alg. 1–3: estimate Q cardinalities in one jitted step.

    ``qs`` is (Q, d), ``taus`` (Q,), ``keys`` (Q, 2) — one PRNG key per query
    so results are bit-identical to Q sequential :func:`estimate` calls with
    the same keys. The hash of all queries is a single (Q, d) @ (d, L·K)
    matmul; per-query ring masks, gathers and the progressive-sampling
    ``while_loop`` are vmapped, so each query carries its own Chernoff
    stopping state while the scan work is shared across the batch
    (DESIGN.md §9). ``pq_luts`` is the pre-built (Q, M, Kc) LUT stack (or a
    batched :class:`~repro.core.pq.QuantLUT`, DESIGN.md §11).

    With ``cfg.lane_block > 0`` (default) and more lanes than one tile
    (``Q·L > cfg.lane_tile``) the loop runs under the skew-resilient
    compacting scheduler (:func:`_estimate_batch_compact`) — bit-identical
    results, mean-lane instead of max-lane wall-clock. A batch that fits
    one tile stays monolithic: compaction cannot retire work at sub-tile
    granularity, so it would be pure overhead there.

    ``axis_name`` (sync mode, DESIGN.md §4): pool the Chernoff statistics
    across the shards of that mesh axis — see :func:`estimate_one_table`.
    The per-lane stopping flags are then shard-invariant, so the vmapped
    while_loop runs the same iteration count on every shard and the in-loop
    psum lines up. Sync mode always uses the monolithic lockstep loop
    (compaction is local-control only — DESIGN.md §11).

    ``with_stats=True`` (static) additionally returns the per-(query,
    table) deepest folded ring ``probed_k`` (Q, L) and per-query pooled
    sample counts ``nvisited`` (Q,) — the provenance the estimate cache
    snapshots for its epoch-invalidation check (DESIGN.md §12). The
    estimates themselves are bit-identical with or without stats.
    """
    n_rings = index.codes.shape[-1]
    if axis_name is None and cfg.lane_block > 0 and \
            qs.shape[0] * index.n_tables > cfg.lane_tile:
        return _estimate_batch_compact(index, x, qs, taus, cfg, keys,
                                       pq_codes=pq_codes, pq_luts=pq_luts,
                                       pq_resid=pq_resid,
                                       pq_packed=pq_packed,
                                       with_stats=with_stats)
    with jax.named_scope("probe/prep"):
        qcodes = lsh.hash_point(index.params, qs, index.n_tables)  # (Q, L, K)
    views = table_views(index)
    use_pq = pq_codes is not None and pq_luts is not None

    def per_query(q, tau, qcode, key, lut):
        tau_sq = jnp.asarray(tau, jnp.float32) ** 2
        qualfn, central_qualfn, exact_qualfn = _make_qualfns(
            x, q, tau_sq, cfg, pq_codes if use_pq else None, lut, pq_resid,
            pq_packed=pq_packed)
        tkeys = jax.random.split(key, index.n_tables)

        def per_table(view, qc, k):
            final = _run_one_table(view, qc, qualfn, cfg, k,
                                   central_qualfn=central_qualfn,
                                   exact_qualfn=exact_qualfn,
                                   axis_name=axis_name)
            return final["est"], final["nvisited"], final["k"]

        ests, nvis, ks = jax.vmap(per_table)(views, qcode, tkeys)
        return (jnp.mean(ests), jnp.clip(ks - 1, 0, n_rings),
                jnp.sum(nvis))

    if not use_pq:
        ests, probed_k, nvis = jax.vmap(
            lambda q, t, qc, k: per_query(q, t, qc, k, None)
        )(qs, taus, qcodes, keys)
    else:
        ests, probed_k, nvis = jax.vmap(per_query)(qs, taus, qcodes, keys,
                                                   pq_luts)
    return (ests, probed_k, nvis) if with_stats else ests
