"""Distributed Dynamic Prober via shard_map (DESIGN.md §4).

Cardinality is additive over a dataset partition, so the estimator is
embarrassingly parallel: shard the points over the ("pod","data") mesh axes,
replicate the LSH/PQ *functions* (so codes are globally consistent), run the
full adaptive prober per shard, and ``psum`` the local estimates.

Two stopping modes (``estimate_sharded(mode=...)``):
  * ``local`` (default) — each shard applies the ε-stopping to its own
    partition; zero mid-query communication. Guarantee: each shard's local
    selectivity is bounded within ε w.p. 1-δ, so the global absolute error is
    bounded by ε·N w.p. (1-δ)^shards (union bound over shards). Each shard
    runs the skew-resilient compacting scheduler (DESIGN.md §11) on its own
    lanes — compaction decisions are purely shard-local, which is exactly
    why this mode permits them.
  * ``sync``  — per sampling round the (w, w') statistics are pooled with a
    psum so the ε test sees global selectivity (one small collective per
    probed slab; see ``prober.estimate_one_table``). The stopping guarantee
    is ε/δ on the GLOBAL selectivity with no union bound, and pooled
    samples reach each doubling anchor shards-times faster. Sync mode keeps
    the monolithic lockstep while_loop: the in-loop psum requires every
    shard to execute the same slab sequence, so lane compaction — whose
    reordering/trip-count decisions would have to be derived from pooled
    values to stay lockstep — is documented local-mode-only (DESIGN.md
    §11) and ``prober.estimate_batch`` routes ``axis_name`` calls to the
    monolithic loop.

Dynamic updates (DESIGN.md §10 extended to the sharded index): a
capacity-padded ``build_sharded(..., capacity=...)`` leaves spare rows on
every shard, and :func:`update_sharded` routes each arriving batch to the
shards round-robin and applies ONE fixed-shape jitted shard_map ingest step
— per-shard ``n_valid`` live counts, W renormalised from the GLOBAL min/max
(a pmin/pmax inside the step), zero new compilations while every shard stays
in capacity, and amortized-doubling growth of all shards together when one
would overflow.
"""
from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import estimator as est_mod
from repro.core import lsh, updates
from repro.core.config import ProberConfig


def _n_shards(mesh: Mesh, data_axes) -> int:
    return int(np.prod([mesh.shape[a] for a in data_axes]))


def _fold_axis_index(key: jax.Array, data_axes) -> jax.Array:
    """Per-shard PRNG key: fold the shard's mesh position into ``key`` so
    shards draw independent PRP round keys / sampling permutations."""
    for ax in data_axes:
        key = jax.random.fold_in(key, jax.lax.axis_index(ax))
    return key


def build_sharded(x_global: jax.Array, cfg: ProberConfig, key: jax.Array,
                  mesh: Mesh, data_axes=("data",),
                  capacity: int | None = None):
    """Build one local index per shard with shared LSH params.

    ``x_global`` is (N, d) with N divisible by the product of ``data_axes``
    sizes. Returns ``(state, params)`` where the state's leaves carry the
    shard dimension first (global leading dim = number of shards).

    ``capacity`` (DESIGN.md §10): GLOBAL row capacity, split evenly over the
    shards — every shard's arrays are padded to ``capacity // n_shards``
    rows so subsequent :func:`update_sharded` calls that fit in the spare
    rows are fixed-shape jitted steps that never recompile.
    """
    # independent keys for the hash functions and the per-shard build
    # sampling — reusing one key here would correlate the LSH projections
    # with the PQ k-means initialisation built from them
    k_params = jax.random.fold_in(key, 0)
    k_build = jax.random.fold_in(key, 1)
    params = lsh.init_params(k_params, x_global.shape[-1], cfg)
    # normalise W on the global dataset (one pass, cheap) so every shard
    # quantises identically — matches Alg. 7's global min/max semantics
    # (pure projections: a later sharded ingest that extends no extreme
    # reproduces this W bitwise, see lsh.py)
    raw = lsh.project_raw(params, x_global)
    params = params._replace(w=lsh.normalize_w(raw, cfg.n_regions))

    shards = _n_shards(mesh, data_axes)
    n = x_global.shape[0]
    assert n % shards == 0, (n, shards)
    cap_shard = None
    if capacity is not None:
        assert capacity % shards == 0, (capacity, shards)
        cap_shard = capacity // shards
        assert cap_shard >= n // shards, (cap_shard, n // shards)

    spec = P(data_axes)
    xs = jax.device_put(x_global, NamedSharding(mesh, spec))

    @jax.shard_map(mesh=mesh, in_specs=(spec, P()), out_specs=spec,
                   check_vma=False)
    def _build(x_local, k):
        k = _fold_axis_index(k, data_axes)
        st = est_mod.build(x_local, cfg, k, params=params,
                           capacity=cap_shard)
        # leading shard axis of size 1 per device -> global leading dim = shards
        return jax.tree_util.tree_map(lambda a: a[None], st)

    state = _build(xs, k_build)
    return state, params


# ------------------------------------------------ sharded dynamic ingest ----

@lru_cache(maxsize=None)
def _sharded_ingest_step(mesh: Mesh, data_axes, cfg: ProberConfig):
    """Jitted fixed-shape shard_map ingest: every shard runs the DESIGN.md
    §10 capacity-padded update on its local slice; the W renormalisation
    pools min/max across shards (one pmin/pmax) so hash codes stay globally
    consistent. Cached per (mesh, axes, cfg) — shapes are the jit cache's
    business, so a steady chunk stream compiles exactly once."""
    spec = P(data_axes)

    def step(st, x_pad, n_new):
        st = jax.tree_util.tree_map(lambda a: a[0], st)   # drop shard axis
        out = est_mod._ingest_core(st, x_pad[0], n_new[0], cfg,
                                   axis_name=data_axes)
        return jax.tree_util.tree_map(lambda a: a[None], out)

    return jax.jit(jax.shard_map(step, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec, check_vma=False))


@lru_cache(maxsize=None)
def _sharded_grow_step(mesh: Mesh, data_axes, new_capacity: int):
    """Amortized-doubling growth of every shard to ``new_capacity`` rows —
    one recompile per doubling, exactly like the single-device path."""
    spec = P(data_axes)

    def step(st):
        st = jax.tree_util.tree_map(lambda a: a[0], st)
        st = est_mod._grow(st, new_capacity)
        return jax.tree_util.tree_map(lambda a: a[None], st)

    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))


def route_round_robin(x_new: np.ndarray, shards: int, offset: int):
    """Deterministic round-robin routing: global arrival ``j`` goes to shard
    ``(offset + j) % shards``, where ``offset`` is the stream position (total
    points ingested so far) — so any arrival order spreads evenly and the
    placement is a pure function of the stream, replayable for audit."""
    return [x_new[((s - offset) % shards)::shards] for s in range(shards)]


def update_sharded(state, x_new, cfg: ProberConfig, mesh: Mesh,
                   data_axes=("data",), n_valid=None):
    """Sharded §5 data updates (Alg. 7/8 per shard, DESIGN.md §4/§10).

    Routes ``x_new`` to the shards round-robin (balanced, deterministic),
    pads every shard's part to one common power-of-two width, and applies
    ONE fixed-shape jitted shard_map ingest step. While every shard stays
    in capacity this is a cached step — zero new compilations (tested in
    tests/test_sharding.py); an overflowing shard doubles ALL shards first
    (uniform shapes, amortized O(log N) recompiles).

    ``n_valid`` is an optional host-side (shards,) array of live counts so
    streaming callers avoid the device_get sync. Returns
    ``(state, n_valid)`` with the updated host-side counts.
    """
    shards = _n_shards(mesh, data_axes)
    if n_valid is None:
        n_valid = np.asarray(jax.device_get(state.index.n_valid))
    nv = np.asarray(n_valid, np.int64).reshape(shards)
    x_new = np.asarray(x_new, np.float32)
    if x_new.ndim == 1:
        x_new = x_new[None]
    d = x_new.shape[-1]

    parts = route_round_robin(x_new, shards, int(nv.sum()) % shards)
    counts = np.asarray([len(p) for p in parts], np.int64)
    width = updates.next_pow2(max(int(counts.max()), 1))
    x_sh = np.zeros((shards, width, d), np.float32)
    for s, part in enumerate(parts):
        x_sh[s, :len(part)] = part

    cap_shard = state.x.shape[1]
    needed = int((nv + counts).max())
    if needed > cap_shard:
        grow = _sharded_grow_step(mesh, tuple(data_axes),
                                  updates.next_capacity(cap_shard, needed))
        state = grow(state)

    spec = NamedSharding(mesh, P(data_axes))
    step = _sharded_ingest_step(mesh, tuple(data_axes), cfg)
    state = step(state,
                 jax.device_put(x_sh, spec),
                 jax.device_put(counts.astype(np.int32), spec))
    return state, nv + counts


# ------------------------------------------------------ sharded serving ----

@lru_cache(maxsize=None)
def _sharded_estimate_step(mesh: Mesh, data_axes, cfg: ProberConfig,
                           mode: str):
    spec = P(data_axes)

    def _est(st, q_all, t_all, k):
        st = jax.tree_util.tree_map(lambda a: a[0], st)  # drop shard axis
        k = _fold_axis_index(k, data_axes)
        if mode == "sync":
            # pooled stopping: the result is already global and replicated
            return est_mod.estimate_batch_pooled(st, q_all, t_all, cfg, k,
                                                 axis_name=data_axes)
        local = est_mod.estimate_batch(st, q_all, t_all, cfg, k)
        return jax.lax.psum(local, data_axes)

    return jax.jit(jax.shard_map(_est, mesh=mesh,
                                 in_specs=(spec, P(), P(), P()),
                                 out_specs=P(), check_vma=False))


def estimate_sharded(state, qs: jax.Array, taus: jax.Array, cfg: ProberConfig,
                     key: jax.Array, mesh: Mesh, data_axes=("data",),
                     mode: str = "local"):
    """Batched distributed estimation over the sharded index.

    ``mode="local"``: each shard runs the full adaptive prober with its own
    ε-stopping; one psum folds the per-shard estimates (zero mid-query
    communication). ``mode="sync"``: pooled-stopping — the per-round (w, w')
    Chernoff statistics are psum'd so the ε-test sees GLOBAL selectivity
    (``estimator.estimate_batch_pooled``). Both return (Q,) estimates.
    """
    assert mode in ("local", "sync"), mode
    step = _sharded_estimate_step(mesh, tuple(data_axes), cfg, mode)
    return step(state, qs, taus, key)
