"""Batched serving engine: static-slot continuous batching over the dense
family's prefill/decode path, plus request coalescing for the estimator.

Small but production-shaped: a request queue, fixed decode slots, per-slot
positions, EOS/timeout retirement, and step-level batching (every decode
step advances all live slots in one jitted call). Used by
examples/serve_semantic.py with a reduced model; the dry-run proves the same
decode lowers at the assigned 32k/500k shapes.

:class:`CardinalityCoalescer` is the cardinality-side analogue (DESIGN.md
§9): concurrent ``(q, tau)`` estimation requests queue up and are flushed
through ONE jitted ``estimate_batch`` step, so the LSH hash matmul, PQ LUT
build and candidate scan are amortised across every in-flight request
instead of being re-dispatched per query.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.cache import estimate_cache as C
from repro.core import estimator as E, lsh, updates
from repro.core.config import ProberConfig
from repro.models import get_family
from repro.models.base import ModelConfig


@dataclasses.dataclass
class CardRequest:
    """One pending cardinality-estimation request."""
    rid: int
    q: np.ndarray                 # (d,) query embedding
    tau: float
    est: Optional[float] = None   # filled by flush()
    provenance: Optional[str] = None   # "probe" | "hit" | "stale-refresh"
                                  # — how flush() produced the estimate
    probed_k: Optional[np.ndarray] = None   # (L,) deepest ring folded per
                                  # table when this request was PROBED
                                  # (None on cache hits — the entry's
                                  # original probe set the rings)
    nvisited: Optional[int] = None     # samples the probe drew (audit)


class CardResult(float):
    """A flush() result value: a float (the estimate) carrying per-request
    provenance so callers can audit what they were served — a fresh probe,
    a cache hit, or a probe that refreshed a stale entry. Compares/serialises
    exactly like the plain float it replaced."""
    provenance: str

    def __new__(cls, est: float, provenance: str = "probe"):
        self = super().__new__(cls, est)
        self.provenance = provenance
        return self


@partial(jax.jit, static_argnames=("n_tables", "reuse_tol", "match_qhash",
                                   "check_ingest"), donate_argnames=("cache",))
def _lookup_step(cache: C.EstimateCache, epochs, params, bucket_codes,
                 bucket_sizes, n_buckets, qs: jax.Array, taus: jax.Array,
                 n: jax.Array, *, n_tables: int, reuse_tol: float,
                 match_qhash: bool, check_ingest: bool):
    """The cached flush's lookup phase as one program: the (p, L, K) bucket
    codes, the query fingerprints and tau keys of the padded batch, the
    live mask of its first ``n`` rows, and ``C.lookup``. Returns ``(cache',
    keys, (hit, stale, est))``; ``keys`` stays on the device for
    :func:`_insert_step`. ``cache`` is donated, as in :func:`_insert_step`:
    the cache returned reuses its buffers, so a call allocates no device
    memory for the cache's arrays."""
    qcodes = lsh.hash_point(params, qs, n_tables)
    keys = (qcodes, C.query_hash(qs), C.tau_band(taus, reuse_tol))
    live = jnp.arange(qs.shape[0]) < n
    cache, est, hit, stale = C.lookup(
        cache, epochs, bucket_codes, bucket_sizes, n_buckets, *keys, live,
        match_qhash=match_qhash, check_ingest=check_ingest)
    return cache, keys, (hit, stale, est)


@partial(jax.jit, static_argnames=("match_qhash",),
         donate_argnames=("cache",))
def _insert_step(cache: C.EstimateCache, epochs, bucket_codes, bucket_sizes,
                 n_buckets, keys, mrows: jax.Array, n_miss: jax.Array,
                 ests: jax.Array, probed_k: jax.Array, nvisited: jax.Array,
                 *, match_qhash: bool):
    """The cached flush's write-back as one program: gather the lookup's
    keys at the miss rows ``mrows`` (padded to the probe's pm lanes; rows
    past ``n_miss`` are inactive) and ``C.insert`` the probe's results.
    Returns ``(cache', n_evicted)``."""
    qcodes, qhash, tkeys = (k[mrows] for k in keys)
    active = jnp.arange(mrows.shape[0]) < n_miss
    return C.insert(cache, epochs, bucket_codes, bucket_sizes, n_buckets,
                    qcodes, qhash, tkeys, ests, nvisited, probed_k, active,
                    match_qhash=match_qhash)


class CardinalityCoalescer:
    """Coalesces concurrent cardinality requests into one jitted step.

    ``submit`` enqueues; ``flush`` pads the pending batch up to the next
    power of two (so at most ``log2(max_batch) + 1`` batch shapes ever
    compile), runs a single ``estimate_batch`` over all of it, and returns
    ``{rid: estimate}``. With the estimate cache the lookup step compiles
    once per batch shape p as well, and the write-back step once per pair
    of p and the padded miss count pm <= p: at most 10 pairs at
    ``max_batch`` 8, and a flush whose lanes all miss uses (p, p). The
    first flush after the first ingest compiles each lookup shape once
    more (its ingest re-check is static). Flush ``i`` derives its PRNG key as
    ``jax.random.fold_in(key, i)``, making a request's estimate a pure
    function of (key, flush index, position in batch) — deterministic and
    replayable for audit.

    Flushes run under the skew-resilient compacting scheduler (DESIGN.md
    §11, ``cfg.lane_block``; engages once a flush spans more than
    ``cfg.lane_tile`` lanes): a coalesced batch mixes independent clients'
    (q, tau) requests, so per-lane work is naturally skewed, and compaction
    keeps one slow request from billing its slab work to every finished
    lane in the flush. The compacting loop is shape-static, so it adds no
    per-flush recompiles (tested in tests/test_compact.py).

    With ``mesh`` (DESIGN.md §4) the coalescer serves off a SHARDED index
    (the state ``distributed.build_sharded`` returns): flushes run the
    distributed ``estimate_sharded`` with the chosen stopping ``mode``
    (``"local"`` per-shard ε-stopping + psum, or ``"sync"`` pooled global
    Chernoff statistics), and :meth:`ingest` routes new points through the
    round-robin sharded recompile-free update step, tracking per-shard live
    counts on the host so dispatch stays async.

    With ``cache_size > 0`` (DESIGN.md §12) each flush first partitions the
    batch against the workload-aware estimate cache: hits are served out of
    the fixed-capacity array cache, only the MISS lanes are probed (a
    smaller ``estimate_batch`` — fewer lanes in means fewer compacted tiles
    run under the §11 scheduler), and fresh results are written back with
    their ingest-epoch snapshots. A hit is served only while no ingest has
    touched any bucket the original probe visited (the O(rings) epoch
    check); ``reuse_tol`` widens the key from exact-repeat to LSH
    near-duplicate matching (see repro/cache). Local (unsharded) serving
    only — the cache keys on this process's index geometry. Per-request
    provenance lands in :class:`CardRequest`/:class:`CardResult`; hit /
    miss / stale / evict counters accumulate in :attr:`cache_stats`. The
    lookup phase (hash, fingerprint, tau key, live mask, ``C.lookup``) is
    one jitted program (:func:`_lookup_step`) and the write-back (key
    gathers at the miss rows, ``C.insert``) another
    (:func:`_insert_step`).

    Instrumentation: every flush runs inside host spans named ``coal/*``
    (``jax.profiler.TraceAnnotation``, on the same clock as the device's
    trace; inert unless a profile is being recorded): ``coal/flush``
    holds ``coal/ingest``, ``coal/pack`` (padding, host-to-device
    copies, the flush's key), ``coal/lookup``, ``coal/probe``,
    ``coal/insert`` and ``coal/merge``, and every blocking device-to-host
    read sits in a ``coal/sync`` span inside the phase that makes it.
    :attr:`stats` counts the work: ``flushes`` (batches stepped),
    ``probe_lanes`` / ``probe_live`` (lanes sent to the prober, padding
    included / live ones among them) and ``syncs`` (device-to-host reads
    while flushing: one per uncached batch; per cached batch one after
    the lookup, and one more in ``coal/merge`` when it probes).
    """

    def __init__(self, state: E.ProberState, cfg: ProberConfig,
                 key: jax.Array, max_batch: int = 256,
                 mesh=None, data_axes=("data",), mode: str = "local",
                 cache_size: int = 0, reuse_tol: float = 0.0):
        assert mode in ("local", "sync"), mode
        assert cache_size == 0 or mesh is None, \
            "the estimate cache serves the local path only (DESIGN.md §12)"
        self.mesh = mesh
        self.data_axes = tuple(data_axes)
        self.mode = mode
        self.cfg = cfg
        self.reuse_tol = float(reuse_tol)
        self._cache = C.init_cache(cache_size, cfg.n_tables, cfg.n_funcs) \
            if cache_size > 0 else None
        self.cache_stats = {"hits": 0, "misses": 0, "stale": 0, "evicts": 0,
                            "lookups": 0}
        self.stats = dict.fromkeys(
            ("flushes", "probe_lanes", "probe_live", "syncs"), 0)
        # host-tracked: False until the first ingest (or external state
        # swap) — lets lookup() statically elide the ball-sum recompute
        # while the corpus is provably unchanged (repro/cache/epochs.py)
        self._check_ingest = False
        self.state = state              # property: also syncs _n_valid
        self._check_ingest = False      # the swap bump above is moot while
                                        # the cache is still empty
        self.key = key
        # round up to a power of two: padding in flush() must never exceed
        # the configured cap, or the compile-shape bound above breaks
        self.max_batch = updates.next_pow2(max_batch)
        self.pending: list[CardRequest] = []
        self._next_rid = 0
        self._n_flushes = 0
        self._answered: dict[int, float] = {}   # auto-flush results not yet
                                                # returned by flush()
        self._ingest_buf: Optional[np.ndarray] = None   # pending new points

    @property
    def state(self) -> E.ProberState:
        return self._state

    @state.setter
    def state(self, st: E.ProberState):
        # re-reads the live count whenever the state is swapped from outside;
        # the internal ingest loop bypasses this (tracking the count on the
        # host) so chunk dispatch never blocks on a device_get
        if self._cache is not None:
            if st.epochs is None:
                st = E.attach_epochs(st)
            # an externally swapped state may hold ARBITRARY new data whose
            # ingests this coalescer never saw — retire the whole cache
            # generation rather than risk a stale hit against it
            st = st._replace(epochs=st.epochs._replace(
                params_epoch=st.epochs.params_epoch + jnp.uint32(1)))
            self._check_ingest = True
        self._state = st
        nv = jax.device_get(st.index.n_valid)
        # sharded states carry one live count per shard
        self._n_valid = np.asarray(nv) if self.mesh is not None else int(nv)

    def submit(self, q, tau) -> CardRequest:
        req = CardRequest(rid=self._next_rid, q=np.asarray(q),
                          tau=float(tau))
        self._next_rid += 1
        self.pending.append(req)
        if len(self.pending) >= self.max_batch:
            self._answered.update(self._drain())
        return req

    # ------------------------------------------------- dynamic ingest -----
    def ingest(self, x_new) -> int:
        """Queue new corpus points (paper §5) for the serving index.

        Points are buffered and applied through the recompile-free
        capacity-padded update step (DESIGN.md §10) in fixed chunks of
        ``cfg.ingest_chunk`` — eagerly once a full chunk accumulates, and
        always before the next estimate flush, so every estimate reflects
        all points ingested before it. Returns the number still buffered.
        """
        x = np.asarray(x_new, np.float32)
        if x.ndim == 1:
            x = x[None]
        self._ingest_buf = x if self._ingest_buf is None else \
            np.concatenate([self._ingest_buf, x], axis=0)
        chunk = self.cfg.ingest_chunk
        while self._ingest_buf is not None and len(self._ingest_buf) >= chunk:
            self._apply_ingest_chunk(chunk)
        return 0 if self._ingest_buf is None else len(self._ingest_buf)

    def apply_ingest(self):
        """Drain the ingest buffer completely (the final partial chunk is
        padded to a power of two inside estimator.update)."""
        chunk = self.cfg.ingest_chunk
        while self._ingest_buf is not None and len(self._ingest_buf) > 0:
            self._apply_ingest_chunk(min(chunk, len(self._ingest_buf)))

    def _apply_ingest_chunk(self, k: int):
        self._check_ingest = True       # lookups must re-check ball sums
        buf = self._ingest_buf
        part, rest = buf[:k], buf[k:]
        self._ingest_buf = rest if len(rest) else None
        with TraceAnnotation("coal/ingest"):
            if self.mesh is not None:
                from repro.core import distributed as D
                self._state, self._n_valid = D.update_sharded(
                    self._state, part, self.cfg, self.mesh,
                    data_axes=self.data_axes, n_valid=self._n_valid)
                return
            self._state = E.update(self._state, jnp.asarray(part), self.cfg,
                                   n_valid=self._n_valid)
            self._n_valid += len(part)

    def _read(self, x):
        """A blocking device-to-host read made while flushing: counted in
        ``stats["syncs"]`` and marked by a ``coal/sync`` span. ``x`` may be
        a tuple of arrays, fetched together as one read."""
        self.stats["syncs"] += 1
        with TraceAnnotation("coal/sync"):
            return jax.device_get(x)

    def flush(self) -> dict[int, float]:
        """Apply pending ingests, then run jitted estimate_batch steps
        (max_batch each) until nothing is pending; returns every answered
        {rid: estimate} not yet returned — including requests already
        answered by a submit()-triggered auto-flush. Values are
        :class:`CardResult` — floats that also carry per-request
        ``provenance`` (``"probe"`` | ``"hit"`` | ``"stale-refresh"``) so
        callers can audit whether an estimate came off a fresh probe or
        the estimate cache."""
        out = self._answered
        self._answered = {}
        out.update(self._drain())
        return out

    def _drain(self) -> dict[int, float]:
        out: dict[int, float] = {}
        if not self.pending and self._ingest_buf is None:
            return out
        with TraceAnnotation("coal/flush"):
            self.apply_ingest()      # estimates see every prior ingest()
            while self.pending:
                batch, self.pending = self.pending[:self.max_batch], \
                    self.pending[self.max_batch:]
                n = len(batch)
                p = updates.next_pow2(n)
                with TraceAnnotation("coal/pack"):
                    d = batch[0].q.shape[-1]
                    qs = np.zeros((p, d), np.float32)
                    taus = np.zeros((p,), np.float32)
                    for i, r in enumerate(batch):
                        qs[i], taus[i] = r.q, r.tau
                    jqs, jtaus = jnp.asarray(qs), jnp.asarray(taus)
                    key = jax.random.fold_in(self.key, self._n_flushes)
                self._n_flushes += 1
                self.stats["flushes"] += 1
                if self._cache is not None:
                    ests, prov, pks, nvs = self._flush_cached(
                        qs, taus, jqs, jtaus, n, key)
                else:
                    self.stats["probe_lanes"] += p
                    self.stats["probe_live"] += n
                    with TraceAnnotation("coal/probe"):
                        if self.mesh is not None:
                            from repro.core import distributed as D
                            ests = self._read(D.estimate_sharded(
                                self.state, jqs, jtaus, self.cfg, key,
                                self.mesh, data_axes=self.data_axes,
                                mode=self.mode))
                        else:
                            ests = self._read(E.estimate_batch(
                                self.state, jqs, jtaus, self.cfg, key))
                    prov = ["probe"] * n
                    pks = nvs = [None] * n
                with TraceAnnotation("coal/merge"):
                    for i, r in enumerate(batch):
                        r.est = float(ests[i])
                        r.provenance = prov[i]
                        r.probed_k, r.nvisited = pks[i], nvs[i]
                        out[r.rid] = CardResult(r.est, prov[i])
        return out

    def _flush_cached(self, qs: np.ndarray, taus: np.ndarray,
                      jqs: jax.Array, jtaus: jax.Array, n: int,
                      key: jax.Array):
        """One flush through the estimate cache (DESIGN.md §12): look every
        request up, probe ONLY the miss lanes (padded to a power of two so
        the §11 compacting scheduler sees at most log2(max_batch) batch
        shapes), write fresh results back with their epoch snapshots, and
        merge. ``jqs``/``jtaus`` are ``qs``/``taus`` on the device.
        Returns ``(ests (n,), provenance (n,), probed_k (n,), nvisited
        (n,))`` — the latter two per-request audit stats (None for hits,
        whose rings were set by the entry's original probe)."""
        st = self._state
        idx = st.index
        strict = self.reuse_tol <= 0.0
        with TraceAnnotation("coal/lookup"):
            self._cache, keys, looked = _lookup_step(
                self._cache, st.epochs, idx.params, idx.bucket_codes,
                idx.bucket_sizes, idx.n_buckets, jqs, jtaus, n,
                n_tables=self.cfg.n_tables, reuse_tol=self.reuse_tol,
                match_qhash=strict, check_ingest=self._check_ingest)
            hit, stale, ests = (a[:n] for a in self._read(looked))
            ests = ests.copy()
        miss = np.nonzero(~hit)[0]
        self.cache_stats["lookups"] += n
        self.cache_stats["hits"] += int(hit.sum())
        self.cache_stats["misses"] += len(miss)
        self.cache_stats["stale"] += int(stale.sum())
        prov = ["hit" if hit[i] else
                ("stale-refresh" if stale[i] else "probe")
                for i in range(n)]
        pks: list = [None] * n
        nvs: list = [None] * n
        if len(miss):
            pm = updates.next_pow2(len(miss))
            self.stats["probe_lanes"] += pm
            self.stats["probe_live"] += len(miss)
            with TraceAnnotation("coal/probe"):
                qs_m = np.zeros((pm, qs.shape[1]), np.float32)
                taus_m = np.zeros((pm,), np.float32)
                qs_m[:len(miss)], taus_m[:len(miss)] = qs[miss], taus[miss]
                jqs_m, jtaus_m = jnp.asarray(qs_m), jnp.asarray(taus_m)
                ests_m, probed_k, nvis = E.estimate_batch_stats(
                    st, jqs_m, jtaus_m, self.cfg, key)
            with TraceAnnotation("coal/insert"):
                # keys for the write-back: the rows already computed for
                # the full-batch lookup (no second hash matmul or
                # fingerprint pass); rows past len(miss) are padding
                mrows = np.pad(miss, (0, pm - len(miss))).astype(np.int32)
                self._cache, n_evict = _insert_step(
                    self._cache, st.epochs, idx.bucket_codes,
                    idx.bucket_sizes, idx.n_buckets, keys, mrows, len(miss),
                    ests_m, probed_k, nvis, match_qhash=strict)
            with TraceAnnotation("coal/merge"):
                n_evict, ests_m, pk_np, nv_np = self._read(
                    (n_evict, ests_m, probed_k, nvis))
                self.cache_stats["evicts"] += int(n_evict)
                ests[miss] = ests_m[:len(miss)]
                for j, i in enumerate(miss):
                    pks[i], nvs[i] = pk_np[j], int(nv_np[j])
        return ests, prov, pks, nvs


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (S,) int32
    max_new: int = 16
    out: list = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    def __init__(self, cfg: ModelConfig, params, batch_slots: int = 4,
                 max_len: int = 256, eos: int = 1):
        assert cfg.family in ("dense",), "engine drives the dense family"
        self.cfg = cfg
        self.fam = get_family(cfg)
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        self.eos = eos
        self.cache = self.fam.init_cache(cfg, batch_slots, max_len)
        # per-slot decode positions: slots prefill at different times with
        # different prompt lengths, so a shared scalar position would make a
        # slot admitted after a longer request write its KV at the wrong row
        # and retire early (RoPE phase and the causal mask also depend on it)
        self.cache["pos"] = jnp.zeros((batch_slots,), jnp.int32)
        self.live: list[Optional[Request]] = [None] * batch_slots
        self.queue: list[Request] = []
        self.finished: list[Request] = []     # retired but not yet returned
        self._decode = jax.jit(
            lambda p, c, t: self.fam.decode_step(p, c, t, cfg))
        self._prefill_one = jax.jit(
            lambda p, b: self.fam.prefill(p, b, cfg, max_len=max_len))

    def submit(self, req: Request):
        self.queue.append(req)

    def _admit(self):
        for i in range(self.slots):
            if self.live[i] is None and self.queue:
                req = self.queue.pop(0)
                cache_i, logits = self._prefill_one(
                    self.params, {"tokens": jnp.asarray(req.prompt)[None, :]})
                # copy the single-sequence cache into slot i; position is
                # per-slot — only slot i takes the new request's length
                self.cache = {
                    "k": self.cache["k"].at[:, i].set(cache_i["k"][:, 0]),
                    "v": self.cache["v"].at[:, i].set(cache_i["v"][:, 0]),
                    "pos": self.cache["pos"].at[i].set(cache_i["pos"]),
                }
                req.out.append(int(jnp.argmax(logits[0])))
                self.live[i] = req

    def step(self):
        """One decode step for every live slot."""
        self._admit()
        if not any(self.live):
            return False
        tokens = jnp.asarray(
            [r.out[-1] if r else 0 for r in self.live], jnp.int32)
        logits, self.cache = self._decode(self.params, self.cache, tokens)
        nxt = np.asarray(jnp.argmax(logits, axis=-1))
        pos = np.asarray(self.cache["pos"])       # already advanced by decode
        for i, req in enumerate(self.live):
            if req is None:
                continue
            tok = int(nxt[i])
            req.out.append(tok)
            if tok == self.eos or len(req.out) >= req.max_new or \
                    int(pos[i]) >= self.max_len - 1:
                req.done = True
                self.live[i] = None
                self.finished.append(req)
        return True

    def run(self, max_steps: int = 512) -> list[Request]:
        """Drive decode steps until idle; returns every request finished
        during the run — tracked as slots retire, so requests that were
        already admitted to a slot before run() or submitted while it is
        stepping are returned too (a queue snapshot at entry would miss
        both)."""
        for _ in range(max_steps):
            if not self.step() and not self.queue:
                break
        finished, self.finished = self.finished, []
        return finished
