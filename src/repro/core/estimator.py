"""DynamicProber — the public API of the paper's contribution.

    state = build(x, cfg, key)                      # offline (Alg. 4/6 + index)
    est   = estimate(state, q, tau, cfg, key)       # online  (Alg. 1/2/3/5)
    ests  = estimate_batch(state, qs, taus, cfg, key)   # batched online path
    state = update(state, x_new, cfg)               # §5      (Alg. 7/8/9)

The state is a pytree (jit/pmap/shard_map friendly). ``use_pq`` switches the
candidate distance function from exact L2 to PQ-ADC ("Dynamic Prober-PQ").

Dynamic serving (DESIGN.md §10): ``build(..., capacity=C)`` produces a
capacity-padded state — arrays sized to C rows with ``n_valid`` live — so
every ``update`` whose points fit in the spare rows is one cached
fixed-shape jitted step (zero new compilations), and ``estimate`` /
``estimate_batch`` keep their compiled steps across updates too (the state's
shapes don't change until a capacity doubling).

Shapes and semantics of the two online entry points:

* ``estimate(state, q, tau, cfg, key) -> ()`` — one query ``q`` of shape
  (d,) and one radius ``tau`` (scalar); returns the scalar estimate of
  ``|{p : ||p - q|| <= tau}|``.
* ``estimate_batch(state, qs, taus, cfg, key) -> (Q,)`` — ``qs`` of shape
  (Q, d) and ``taus`` of shape (Q,); ``key`` is split into Q per-query keys,
  so the result is bit-identical to Q sequential ``estimate`` calls with
  ``jax.random.split(key, Q)[i]`` (tested in tests/test_batched.py). The
  batch shares one jitted step: the LSH hash matmul, PQ LUT construction and
  the candidate scan are amortised across queries while each query keeps its
  own Chernoff stopping state (DESIGN.md §9).

Error model (paper §4.5): with ``eps`` and ``delta`` from the config, each
ring's progressive sampler stops once the Chernoff interval around the
empirical selectivity is within ``eps`` on both sides, each side holding
with probability ``1 - delta`` (``a = ln(1/delta)``). Smaller ``eps`` /
``delta`` mean more samples and tighter estimates.

Usage::

    import jax, jax.numpy as jnp
    from repro.core import estimator as E
    from repro.core.config import ProberConfig

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (8192, 128))          # the corpus
    cfg = ProberConfig(n_tables=2, n_funcs=10)
    state = E.build(x, cfg, key)

    est = E.estimate(state, x[0], jnp.float32(9.0), cfg, key)   # one query
    qs, taus = x[:64], jnp.full((64,), 9.0)                     # a batch
    ests = E.estimate_batch(state, qs, taus, cfg, key)          # (64,)
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.cache import epochs as cache_epochs
from repro.core import lsh, pq as pqmod, prober, updates
from repro.core.config import ProberConfig


class ProberState(NamedTuple):
    index: lsh.LSHIndex
    x: jax.Array                      # (C, d) the dataset (exact distances;
                                      #   rows >= n_valid are capacity padding)
    pq: Optional[pqmod.PQIndex]       # None unless cfg.use_pq
    epochs: Optional["cache_epochs.EpochState"] = None
                                      # ingest-epoch counters for the serving
                                      # estimate cache (DESIGN.md §12); None
                                      # unless attached via track_epochs /
                                      # attach_epochs — updates bump them
                                      # inside the same fixed-shape step

    @property
    def n_valid(self) -> jax.Array:
        """Live point count — rows below this index are real data
        (DESIGN.md §10)."""
        return self.index.n_valid

    @property
    def capacity(self) -> int:
        return self.x.shape[0]


def stored_width(d: int) -> int:
    """Columns of the capacity-padded corpus for published dimension ``d``:
    ``d`` rounded up to a multiple of 128, the TPU's lane width. A corpus
    whose width is not such a multiple gets a column-major default layout
    on the chip, and the probe's row gathers would then copy the whole
    corpus to row-major on every call; the zero columns past ``d`` add
    exactly 0 to every squared distance, and the hash functions and PQ
    subspaces stay on the ``d`` published dimensions."""
    return -(-d // 128) * 128


def build(x: jax.Array, cfg: ProberConfig, key: jax.Array,
          params: lsh.LSHParams | None = None,
          capacity: int | None = None,
          track_epochs: bool = False) -> ProberState:
    """Offline build. With ``capacity`` (DESIGN.md §10) the state is
    capacity-padded: arrays sized to ``capacity`` rows with ``x.shape[0]``
    live, so subsequent :func:`update` calls that fit in the spare rows are
    fixed-shape jitted steps that never recompile; the corpus is stored
    :func:`stored_width` columns wide. ``track_epochs`` attaches
    the serving cache's ingest-epoch counters (DESIGN.md §12) so every
    update also records which buckets it touched. The phases run in the
    host spans ``build/pad``, ``build/lsh``, ``build/pq_fit`` and
    ``build/pq_encode`` (codes, counts and residuals)."""
    k1, k2 = jax.random.split(key)
    if capacity is None:
        index = lsh.build_index(x, cfg, k1, params=params)
        pq = pqmod.fit(x, cfg, k2) if cfg.use_pq else None
        state = ProberState(index=index, x=x, pq=pq)
    else:
        n, d = x.shape
        assert capacity >= n, (capacity, n)
        # each phase ends on the device inside its span
        with TraceAnnotation("build/pad"):
            x_pad = jax.block_until_ready(jnp.pad(
                jnp.asarray(x, jnp.float32),
                ((0, capacity - n), (0, stored_width(d) - d))))
        with TraceAnnotation("build/lsh"):
            index = jax.block_until_ready(lsh.build_index(
                x_pad, cfg, k1, params=params, n_valid=n, dim=d))
        pq = None
        if cfg.use_pq:
            with TraceAnnotation("build/pq_fit"):
                centroids = jax.block_until_ready(
                    pqmod.kmeans(x_pad, cfg, k2, n_valid=n, dim=d))
            with TraceAnnotation("build/pq_encode"):
                pq = jax.block_until_ready(
                    pqmod.encode(x_pad, centroids, cfg, n_valid=n))
        state = ProberState(index=index, x=x_pad, pq=pq)
    return attach_epochs(state) if track_epochs else state


def attach_epochs(state: ProberState) -> ProberState:
    """Attach (fresh) ingest-epoch state (DESIGN.md §12) so subsequent
    :func:`update` calls maintain it inside the same fixed-shape jitted
    ingest step. Counters start at zero — correct for a cache created at
    (or after) the same moment."""
    return state._replace(epochs=cache_epochs.init_epochs())


@partial(jax.jit, static_argnames=("cfg",))
def estimate(state: ProberState, q: jax.Array, tau: jax.Array,
             cfg: ProberConfig, key: jax.Array) -> jax.Array:
    if cfg.use_pq and state.pq is not None:
        lut = pqmod.build_query_lut(state.pq, q, cfg)
        return prober.estimate(state.index, state.x, q, tau, cfg, key,
                               pq_codes=state.pq.codes, pq_lut=lut,
                               pq_resid=state.pq.resid,
                               pq_packed=state.pq.packed)
    return prober.estimate(state.index, state.x, q, tau, cfg, key)


@jax.named_scope("probe/prep")
def _query_luts(pq: pqmod.PQIndex, qs: jax.Array, cfg: ProberConfig):
    """Per-query PQ LUTs of a batch: the (Q, M, Kc) float stack, or a
    batched QuantLUT (DESIGN.md §11)."""
    return jax.vmap(lambda q: pqmod.build_query_lut(pq, q, cfg))(qs)


@partial(jax.jit, static_argnames=("cfg",))
def estimate_batch(state: ProberState, qs: jax.Array, taus: jax.Array,
                   cfg: ProberConfig, key: jax.Array) -> jax.Array:
    """Estimate Q cardinalities in one jitted step (see module docstring)."""
    keys = jax.random.split(key, qs.shape[0])
    if cfg.use_pq and state.pq is not None:
        luts = _query_luts(state.pq, qs, cfg)
        return prober.estimate_batch(state.index, state.x, qs, taus, cfg, keys,
                                     pq_codes=state.pq.codes, pq_luts=luts,
                                     pq_resid=state.pq.resid,
                                     pq_packed=state.pq.packed)
    return prober.estimate_batch(state.index, state.x, qs, taus, cfg, keys)


@partial(jax.jit, static_argnames=("cfg",))
def estimate_batch_stats(state: ProberState, qs: jax.Array, taus: jax.Array,
                         cfg: ProberConfig, key: jax.Array):
    """:func:`estimate_batch` plus probe provenance: returns
    ``(ests (Q,), probed_k (Q, L), nvisited (Q,))`` where ``probed_k`` is
    the deepest ring each (query, table) lane folded — what the serving
    estimate cache snapshots for epoch invalidation (DESIGN.md §12).
    Estimates are bit-identical to :func:`estimate_batch` with the same
    key."""
    keys = jax.random.split(key, qs.shape[0])
    if cfg.use_pq and state.pq is not None:
        luts = _query_luts(state.pq, qs, cfg)
        return prober.estimate_batch(state.index, state.x, qs, taus, cfg,
                                     keys, pq_codes=state.pq.codes,
                                     pq_luts=luts, pq_resid=state.pq.resid,
                                     pq_packed=state.pq.packed,
                                     with_stats=True)
    return prober.estimate_batch(state.index, state.x, qs, taus, cfg, keys,
                                 with_stats=True)


def estimate_batch_pooled(state: ProberState, qs: jax.Array, taus: jax.Array,
                          cfg: ProberConfig, key: jax.Array,
                          axis_name) -> jax.Array:
    """Distributed "sync" stopping mode (DESIGN.md §4): ``estimate_batch``
    with the per-round (w, w') Chernoff statistics pooled across the shards
    of the mesh axis ``axis_name``, so the ε-test sees GLOBAL selectivity.

    Must be called *inside* a shard_map over ``axis_name`` with ``state``
    holding the local shard (``distributed.estimate_sharded(mode="sync")``
    is the public entry point). Returns the global (Q,) estimates,
    replicated on every shard — no trailing psum needed.
    """
    keys = jax.random.split(key, qs.shape[0])
    axis_name = axis_name if isinstance(axis_name, str) else tuple(axis_name)
    if cfg.use_pq and state.pq is not None:
        luts = _query_luts(state.pq, qs, cfg)
        return prober.estimate_batch(state.index, state.x, qs, taus, cfg,
                                     keys, pq_codes=state.pq.codes,
                                     pq_luts=luts, pq_resid=state.pq.resid,
                                     pq_packed=state.pq.packed,
                                     axis_name=axis_name)
    return prober.estimate_batch(state.index, state.x, qs, taus, cfg, keys,
                                 axis_name=axis_name)


def _ingest_core(state: ProberState, x_pad: jax.Array, n_new: jax.Array,
                 cfg: ProberConfig, axis_name=None) -> ProberState:
    """One fixed-shape §5 update: write the new rows into spare capacity,
    re-run Alg. 7 over the padded layout, and Alg. 8 with residual refresh.
    Every output shape equals the input shape, so in-capacity updates reuse
    one compiled step (DESIGN.md §10). The single shared body for the
    single-device (:func:`update`) and sharded
    (``distributed.update_sharded``) paths — ``axis_name`` pools Alg. 7's W
    renormalisation across that mesh axis (DESIGN.md §4). When the state
    carries epoch counters (DESIGN.md §12) they are bumped here too, so the
    cache-invalidation signal rides the same zero-recompile step."""
    nv = state.index.n_valid
    old_w = state.index.params.w
    wide = jnp.pad(x_pad, ((0, 0), (0, state.x.shape[-1] - x_pad.shape[-1])))
    x = updates._write_rows(state.x, wide, nv, n_new)
    index = updates._lsh_ingest(state.index, x_pad, n_new, cfg,
                                axis_name=axis_name)
    pq = updates._pq_ingest(state.pq, x, x_pad, n_new) \
        if state.pq is not None else None
    ep = updates._epoch_ingest(state.epochs, index, old_w, n_new) \
        if state.epochs is not None else None
    return ProberState(index=index, x=x, pq=pq, epochs=ep)


_ingest_step = jax.jit(_ingest_core, static_argnames=("cfg", "axis_name"))


def _grow(state: ProberState, new_capacity: int) -> ProberState:
    """Amortized-doubling capacity growth: re-pad every per-point array and
    rebuild the (untrimmed) bucket layout at the new capacity. Recompiles —
    by design only O(log N) times over any update stream."""
    cap = state.x.shape[0]
    x = jnp.pad(state.x, ((0, new_capacity - cap), (0, 0)))
    index = lsh.grow_capacity(state.index, new_capacity)
    pq = pqmod.grow(state.pq, new_capacity) if state.pq is not None else None
    # epoch counters are keyed by code VALUE, not row, so growth (which
    # moves no live point and changes no code) carries them verbatim —
    # cache entries stay valid across doublings (DESIGN.md §12)
    return ProberState(index=index, x=x, pq=pq, epochs=state.epochs)


def update(state: ProberState, x_new: jax.Array, cfg: ProberConfig,
           n_valid: int | None = None) -> ProberState:
    """§5 data updates for every component of the framework.

    If the new points fit in spare capacity this is ONE cached jitted step
    — zero new compilations (the recompile-free serving contract, tested in
    tests/test_updates.py). Otherwise capacity doubles first. The batch is
    padded to the next power of two, so at most log2(max batch) ingest
    shapes ever compile per capacity.

    ``n_valid`` is an optional host-side hint of the current live count:
    reading it from the device blocks on the previous step's results, so
    streaming callers (the serve-layer ingest loop) track the count on the
    host and keep dispatch fully async.
    """
    nn = x_new.shape[0]
    nv = int(jax.device_get(state.index.n_valid)) if n_valid is None \
        else int(n_valid)
    cap = state.x.shape[0]
    if nv + nn > cap:
        state = _grow(state, updates.next_capacity(cap, nv + nn))
    x_pad, n_new = updates._pad_batch(x_new)
    return _ingest_step(state, x_pad, n_new, cfg)


def true_cardinality(x: jax.Array, q: jax.Array, tau: jax.Array,
                     n_valid: jax.Array | None = None) -> jax.Array:
    """Exact ground truth (for tests/benchmarks). ``n_valid`` masks the
    capacity-padding rows of a padded corpus; ``x`` may be stored wider
    than ``q`` (:func:`stored_width`)."""
    q = jnp.pad(q, (0, x.shape[-1] - q.shape[-1]))
    d2 = jnp.sum((x - q[None, :]) ** 2, axis=-1)
    hit = d2 <= jnp.asarray(tau, jnp.float32) ** 2
    if n_valid is not None:
        hit = hit & (jnp.arange(x.shape[0]) < n_valid)
    return jnp.sum(hit)
