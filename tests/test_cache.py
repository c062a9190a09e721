"""Workload-aware estimate cache (DESIGN.md §12).

The contracts under test: exact-repeat hits are BIT-IDENTICAL to the
estimate the original probe produced; any ingest touching a probed bucket
forces a re-probe and NO stale hit is ever served (checked against an
exact shadow tracker over a mixed ingest+query stream, including across
capacity-doubling growth); `reuse_tol` bands tau and relaxes the exact-
query fingerprint; CLOCK eviction prefers cold entries; repeated all-hit
flushes add zero XLA compilations; the coalescer's jitted lookup and
write-back steps give, bit for bit, what the eager library calls give;
and flush() reports per-request provenance.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from conftest import compile_events
from jax import monitoring

from repro.cache import estimate_cache as C
from repro.core import estimator as E, lsh
from repro.core.config import ProberConfig
from repro.core.updates import next_pow2
from repro.serve.engine import CardinalityCoalescer

CFG = ProberConfig(n_tables=2, n_funcs=6, ring_budget=512,
                   central_budget=512, chunk=128)


@pytest.fixture(scope="module")
def data():
    return np.asarray(jax.random.normal(jax.random.PRNGKey(0), (2048, 16)))


def _coalescer(data, cfg=CFG, n=1024, capacity=4096, cache_size=64,
               reuse_tol=0.0, max_batch=8, seed=0):
    key = jax.random.PRNGKey(seed)
    st_ = E.build(jnp.asarray(data[:n]), cfg, key, capacity=capacity,
                  track_epochs=True)
    return CardinalityCoalescer(st_, cfg, key, max_batch=max_batch,
                                cache_size=cache_size, reuse_tol=reuse_tol)


def test_exact_repeat_hits_bit_identical(data):
    """reuse_tol=0 contract: a repeat of the same (q, tau) is served from
    the cache, bit-identical to what the original probe returned, with
    provenance the caller can audit."""
    co = _coalescer(data)
    qs = [data[i] + 0.01 for i in range(5)]
    taus = [3.0, 4.0, 5.0, 3.5, 4.5]
    first = [co.submit(qs[i], taus[i]) for i in range(5)]
    out0 = co.flush()
    assert all(r.provenance == "probe" for r in first)
    assert all(out0[r.rid].provenance == "probe" for r in first)
    again = [co.submit(qs[i], taus[i]) for i in range(5)]
    out1 = co.flush()
    for a, b in zip(first, again):
        assert b.provenance == "hit"
        assert out1[b.rid].provenance == "hit"
        assert a.est == b.est                      # bit-identical, not close
    assert co.cache_stats["hits"] == 5
    assert co.cache_stats["misses"] == 5
    # a different tau (even slightly) is NOT the same request
    r = co.submit(qs[0], taus[0] + 1e-3)
    co.flush()
    assert r.provenance == "probe"


def test_near_duplicate_query_misses_at_tol_zero(data):
    """reuse_tol=0 is fully strict: a query differing in one float bit of
    one coordinate misses even though its LSH codes collide."""
    co = _coalescer(data)
    q = data[3] + 0.01
    co.submit(q, 4.0)
    co.flush()
    q2 = q.copy()
    q2[0] = np.nextafter(q2[0], np.inf)            # same bucket, new bytes
    r = co.submit(q2, 4.0)
    co.flush()
    assert r.provenance == "probe"


def test_reuse_tol_bands_tau_and_lsh_keys(data):
    """reuse_tol>0: hits extend to the same tau band and to LSH
    near-duplicates (identical codes in every table)."""
    co = _coalescer(data, reuse_tol=0.3)
    q = data[7] + 0.01
    co.submit(q, 5.0)
    co.flush()
    r_band = co.submit(q, 5.5)                     # same (1+0.3) log-band
    co.flush()
    assert r_band.provenance == "hit"
    r_far = co.submit(q, 8.0)                      # different band
    co.flush()
    assert r_far.provenance == "probe"
    # a tiny perturbation keeps all bucket codes -> near-duplicate hit
    q2 = q + 1e-6
    codes_same = np.array_equal(
        np.asarray(lsh.hash_point(co.state.index.params, jnp.asarray(q),
                                  CFG.n_tables)),
        np.asarray(lsh.hash_point(co.state.index.params, jnp.asarray(q2),
                                  CFG.n_tables)))
    r_near = co.submit(q2, 5.0)
    co.flush()
    assert r_near.provenance == ("hit" if codes_same else "probe")


def test_ingest_into_probed_bucket_invalidates(data):
    """Epoch invalidation: an ingest landing AT a cached query's location
    (its central bucket) must force a re-probe whose estimate sees the new
    points."""
    cfg = CFG.replace(ingest_chunk=64)
    co = _coalescer(data, cfg=cfg)
    q = data[0] + 50.0                             # isolated: est ~ 0
    r0 = co.submit(q, 3.0)
    co.flush()
    assert r0.est < 1.0
    cluster = q[None, :] + 0.05 * np.asarray(
        jax.random.normal(jax.random.PRNGKey(1), (128, 16)))
    co.ingest(cluster.astype(np.float32))
    r1 = co.submit(q, 3.0)
    co.flush()
    assert r1.provenance in ("stale-refresh", "probe")
    assert r1.est > 50.0, r1.est                   # the cluster is visible


class _ShadowTracker:
    """Exact mirror of what MAY be served from cache: for every cached key
    it recomputes, from the index itself, whether any ingest since the
    entry's probe landed within the entry's probed rings. A `hit` for a
    dirty key is a stale serve — the property the epoch layer must make
    impossible."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.entries: dict = {}     # (qbytes, tau) -> {probed_k, est, w}

    def _codes(self, state, q):
        return np.asarray(lsh.hash_point(state.index.params,
                                         jnp.asarray(q), self.cfg.n_tables))

    def record_probe(self, state, req):
        assert req.probed_k is not None     # every probe reports its rings
        self.entries[(req.q.tobytes(), req.tau)] = {
            "qcodes": self._codes(state, req.q),
            "w": np.asarray(state.index.params.w).copy(),
            "probed_k": np.asarray(req.probed_k),
            "dirty": False, "est": req.est}

    def note_ingest(self, state_after, x_new):
        new_codes = np.asarray(lsh.hash_point(
            state_after.index.params, jnp.asarray(x_new),
            self.cfg.n_tables))                     # (Nn, L, K)
        w_now = np.asarray(state_after.index.params.w)
        for e in self.entries.values():
            if not np.array_equal(e["w"], w_now):
                e["dirty"] = True                   # geometry changed
                continue
            # distance of each new point's bucket to the entry's code: the
            # entry depends EXACTLY on buckets within its probed rings
            d = (new_codes != e["qcodes"][None]).sum(-1)   # (Nn, L)
            if (d.min(0) <= e["probed_k"]).any():
                e["dirty"] = True

    def check_serve(self, req):
        e = self.entries.get((req.q.tobytes(), req.tau))
        if req.provenance == "hit":
            assert e is not None, "hit without a recorded probe"
            assert not e["dirty"], "STALE SERVE: ingest touched probed rings"
            assert req.est == e["est"], "hit diverged from recorded estimate"


def test_zero_stale_serves_mixed_stream(data):
    """The acceptance property: over a mixed ingest+query stream —
    crossing a capacity doubling — every `hit` the coalescer serves is for
    an entry whose probed rings no ingest has touched (exact shadow
    check), and hits still actually happen (the test is not vacuous)."""
    cfg = CFG.replace(ingest_chunk=64)
    rng = np.random.default_rng(0)
    # capacity == n: the ingest stream forces grow_capacity doublings
    co = _coalescer(data, cfg=cfg, n=1024, capacity=1024, cache_size=128,
                    max_batch=16)
    shadow = _ShadowTracker(cfg)
    qpool = [data[i] + 0.01 for i in range(12)]
    taupool = [3.0, 4.0, 5.0]
    n_hits = 0
    for step in range(30):
        if step % 5 == 4:
            x_new = data[rng.integers(0, 2048, 48)] + \
                0.1 * rng.standard_normal((48, 16)).astype(np.float32)
            co.ingest(x_new)
            co.apply_ingest()
            shadow.note_ingest(co.state, x_new)
        reqs = [co.submit(qpool[rng.integers(len(qpool))],
                          taupool[rng.integers(len(taupool))])
                for _ in range(4)]
        co.flush()
        for r in reqs:
            shadow.check_serve(r)
            if r.provenance == "hit":
                n_hits += 1
            else:
                shadow.record_probe(co.state, r)
    assert int(co.state.n_valid) > 1024            # stream actually grew
    assert co.state.capacity > 1024                # ... through doublings
    assert n_hits > 0, "no hits at all — the property test is vacuous"
    assert co.cache_stats["hits"] == n_hits


def test_entries_survive_growth_without_ingest_overlap(data):
    """Capacity doubling itself must not invalidate entries — epochs key on
    code values, not rows, and W is bitwise-stable when no projection
    extreme moves (lsh.project_raw). Construction: a budget-truncated
    probe (small ``probed_k``), then an ingest of MIDPOINTS of live points
    (convex combinations — provably inside every per-function projection
    range, so Alg. 7 reproduces W exactly) FILTERED to bucket codes
    outside the entry's probed rings. The ingest forces a doubling, yet
    the entry keeps serving bit-identical hits."""
    cfg = CFG.replace(ingest_chunk=64, max_visit=256)   # shallow probes
    co = _coalescer(data, cfg=cfg, n=1024, capacity=1024, max_batch=8)
    q = data[0] + 0.01              # dense region: budget stops the probe
    r0 = co.submit(q, 3.0)
    co.flush()
    assert r0.probed_k is not None and r0.probed_k.max() < CFG.n_funcs, \
        "probe was not truncated — the test needs a small ball"
    epoch0 = int(co.state.epochs.params_epoch)
    mids = 0.5 * (data[:512] + data[512:1024])     # inside all extremes
    qc = np.asarray(lsh.hash_point(co.state.index.params, jnp.asarray(q),
                                   cfg.n_tables))              # (L, K)
    mc = np.asarray(lsh.hash_point(co.state.index.params,
                                   jnp.asarray(mids), cfg.n_tables))
    outside = ((mc != qc[None]).sum(-1) > r0.probed_k[None, :]).all(-1)
    mids = mids[outside]
    assert len(mids) >= 64, "not enough out-of-ball midpoints"
    co.ingest(mids.astype(np.float32))             # forces capacity growth
    co.apply_ingest()
    assert co.state.capacity > 1024
    assert int(co.state.epochs.params_epoch) == epoch0, \
        "W drifted on an ingest that extended no projection extreme"
    r1 = co.submit(q, 3.0)
    co.flush()
    assert r1.provenance == "hit"
    assert r1.est == r0.est


def test_clock_eviction_prefers_cold_entries(data):
    """Second chance: with a 4-entry cache and 4 cached keys, touching one
    key (a hit re-arms its ref bit) then inserting new keys must evict
    among the untouched ones first."""
    co = _coalescer(data, cache_size=4, max_batch=4)
    qs = [data[i] + 0.01 for i in range(7)]
    for i in range(4):
        co.submit(qs[i], 4.0)
        co.flush()
    hot = co.submit(qs[0], 4.0)                    # touch entry 0
    co.flush()
    assert hot.provenance == "hit"
    for i in range(4, 7):                          # 3 insertions, 3 evicts
        co.submit(qs[i], 4.0)
        co.flush()
    assert co.cache_stats["evicts"] == 3
    still_hot = co.submit(qs[0], 4.0)
    co.flush()
    assert still_hot.provenance == "hit", \
        "the touched entry was evicted before the cold ones"


def test_all_hit_flush_zero_recompiles(data):
    """Serving contract: once the flush shapes are warm, an all-hit flush
    (and the lookup partition step of a mixed flush) adds ZERO new XLA
    compilations — the cache hot path is pure cached executables."""
    co = _coalescer(data, max_batch=8)
    qs = [data[i] + 0.01 for i in range(4)]
    for q in qs:
        co.submit(q, 4.0)
    co.flush()                                     # warm probe + insert
    for q in qs:
        co.submit(q, 4.0)
    co.flush()                                     # warm all-hit lookup
    with compile_events() as ev:
        for q in qs:
            co.submit(q, 4.0)
        out = co.flush()
    assert len(out) == 4
    assert all(v.provenance == "hit" for v in out.values())
    assert ev == [], f"all-hit flush recompiled: {ev}"


def test_cached_results_match_uncached_distribution(data):
    """meanQ-preservation mechanism: with no repeats in the stream the
    cached coalescer produces the SAME estimates as an uncached one (the
    cache must not perturb the probe path it wraps)."""
    key = jax.random.PRNGKey(3)
    st_ = E.build(jnp.asarray(data[:1024]), CFG, key, capacity=2048,
                  track_epochs=True)
    a = CardinalityCoalescer(st_, CFG, key, max_batch=8, cache_size=64)
    b = CardinalityCoalescer(st_, CFG, key, max_batch=8)
    qs = [data[i] + 0.01 for i in range(6)]
    ra = [a.submit(q, 4.0) for q in qs]
    rb = [b.submit(q, 4.0) for q in qs]
    a.flush()
    b.flush()
    for x, y in zip(ra, rb):
        assert x.est == y.est


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=2047),
       st.floats(min_value=0.5, max_value=8.0, allow_nan=False,
                 width=32))
def test_property_repeat_hit_equals_first_serve(idx, tau):
    """Property (hypothesis): for ANY (query, tau), serving the request
    twice yields provenance probe-then-hit with bit-identical estimates."""
    data = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (2048, 16)))
    co = _coalescer(data, cache_size=32, max_batch=4)
    q = data[idx] + 0.01
    r0 = co.submit(q, float(tau))
    co.flush()
    r1 = co.submit(q, float(tau))
    co.flush()
    assert r0.provenance == "probe" and r1.provenance == "hit"
    assert r0.est == r1.est


class _EagerCoalescer(CardinalityCoalescer):
    """Shadow of the cached flush as the library calls compose it eagerly:
    the hash, ``C.query_hash``, ``C.tau_band``, the live mask and
    ``C.lookup`` as separate dispatches, the probe, then the key gathers
    and ``C.insert``, with the same key. The coalescer's jitted lookup and
    write-back steps must reproduce it bit for bit."""

    def _flush_cached(self, qs, taus, jqs, jtaus, n, key):
        st_ = self._state
        strict = self.reuse_tol <= 0.0
        qcodes = lsh.hash_point(st_.index.params, jqs, self.cfg.n_tables)
        qhash = C.query_hash(jqs)
        tkeys = C.tau_band(jtaus, self.reuse_tol)
        live = jnp.arange(qs.shape[0]) < n
        self._cache, c_est, hit, stale = C.lookup(
            self._cache, st_.epochs, st_.index.bucket_codes,
            st_.index.bucket_sizes, st_.index.n_buckets, qcodes, qhash,
            tkeys, live, match_qhash=strict,
            check_ingest=self._check_ingest)
        hit, stale = np.asarray(hit)[:n], np.asarray(stale)[:n]
        ests = np.asarray(c_est)[:n].copy()
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
            pm = next_pow2(len(miss))
            qs_m = np.zeros((pm, qs.shape[1]), np.float32)
            taus_m = np.zeros((pm,), np.float32)
            qs_m[:len(miss)], taus_m[:len(miss)] = qs[miss], taus[miss]
            ests_m, probed_k, nvis = E.estimate_batch_stats(
                st_, jnp.asarray(qs_m), jnp.asarray(taus_m), self.cfg, key)
            mrows = jnp.asarray(np.pad(miss, (0, pm - len(miss))))
            self._cache, n_evict = C.insert(
                self._cache, st_.epochs, st_.index.bucket_codes,
                st_.index.bucket_sizes, st_.index.n_buckets,
                qcodes[mrows], qhash[mrows], tkeys[mrows], ests_m, nvis,
                probed_k, jnp.arange(pm) < len(miss), match_qhash=strict)
            self.cache_stats["evicts"] += int(n_evict)
            ests[miss] = np.asarray(ests_m)[:len(miss)]
            pk_np, nv_np = np.asarray(probed_k), np.asarray(nvis)
            for j, i in enumerate(miss):
                pks[i], nvs[i] = pk_np[j], int(nv_np[j])
        return ests, prov, pks, nvs


@pytest.mark.parametrize("reuse_tol", [0.0, 0.25])
def test_jitted_steps_match_eager_shadow(data, reuse_tol):
    """Same submits, ingests and flushes into the coalescer and its eager
    shadow: every estimate, provenance, probed ring depth and sample count,
    the cache counters and the final cache arrays are bit-identical. The
    stream holds repeats, near-repeats (one float bit off, tau 1% off:
    hits only under ``reuse_tol`` > 0), fresh queries, CLOCK evictions
    and an ingest next to a cached query, after which that query is
    refreshed as stale. One request has the key of a padding lane (a zero
    query at tau 0): padding lanes of later flushes must not touch it."""
    cfg = CFG.replace(ingest_chunk=64)
    key = jax.random.PRNGKey(5)
    st_ = E.build(jnp.asarray(data[:1024]), cfg, key, capacity=4096,
                  track_epochs=True)
    cos = [cls(st_, cfg, key, max_batch=8, cache_size=8,
               reuse_tol=reuse_tol)
           for cls in (CardinalityCoalescer, _EagerCoalescer)]
    qs = [data[i] + 0.01 for i in range(14)]
    near = qs[2].copy()
    near[0] = np.nextafter(near[0], np.inf)
    cluster = (qs[0][None, :] + 0.05 * np.asarray(jax.random.normal(
        jax.random.PRNGKey(1), (64, 16)))).astype(np.float32)
    stream = [
        [(qs[i], t) for i, t in zip(range(5), (3.0, 4.0, 5.0, 3.5, 4.5))]
        + [(np.zeros(16, np.float32), 0.0)],
        [(qs[0], 3.0), (qs[1], 4.0), (near, 5.0 * 1.01), (qs[5], 4.0),
         (qs[6], 4.0)],
        [(qs[0], 3.0)] + [(qs[i], 4.0) for i in range(7, 12)],
        "ingest",
        [(qs[0], 3.0), (qs[1], 4.0), (qs[9], 4.0), (qs[12], 2.5)],
        [(qs[0], 3.0), (qs[13], 4.0), (qs[3], 3.5), (qs[11], 4.0),
         (qs[1], 4.0), (qs[2], 5.0), (qs[4], 4.5), (qs[5], 4.0)],
    ]
    for step in stream:
        if step == "ingest":
            for co in cos:
                co.ingest(cluster)
            continue
        got = [[co.submit(q, t) for q, t in step] for co in cos]
        for co in cos:
            co.flush()
        for a, b in zip(*got):
            assert (a.est, a.provenance, a.nvisited) == \
                (b.est, b.provenance, b.nvisited)
            assert (a.probed_k is None) == (b.probed_k is None)
            if a.probed_k is not None:
                assert np.array_equal(a.probed_k, b.probed_k)
    co, shadow = cos
    assert co.cache_stats == shadow.cache_stats
    for name, x, y in zip(C.EstimateCache._fields, co._cache,
                          shadow._cache):
        assert np.array_equal(np.asarray(x), np.asarray(y)), name
    cs = co.cache_stats
    assert cs["hits"] > 0 and cs["stale"] > 0 and cs["evicts"] > 0, cs


def test_cached_flush_compiles_one_program_per_phase():
    """The first cached flush at a fresh shape (d = 40 and a 40-entry cache,
    which no other test uses) compiles the lookup step, the probe, the
    write-back step and, if this process has not yet, ``fold_in``: at
    most 4 programs. With the eager lookup and write-back the same flush
    compiled 32: the probe and ``fold_in``, and 30 for the hash, each op
    of the fingerprint, the tau band and the masks, each key gather,
    ``lookup`` and ``insert``. A second flush of the same shapes compiles
    nothing."""
    names: list = []

    def named(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            names.append(kw.get("fun_name"))

    x = np.asarray(jax.random.normal(jax.random.PRNGKey(2), (600, 40)))
    co = _coalescer(x, n=512, capacity=1024, cache_size=40)
    jax.block_until_ready(co.state)
    monitoring.register_event_duration_secs_listener(named)
    try:
        for rep in range(2):
            names.clear()
            with compile_events() as ev:
                for i in range(3):
                    co.submit(x[512 + 3 * rep + i], 4.0)
                co.flush()
            if rep == 0:
                steps = {"jit(_lookup_step)", "jit(estimate_batch_stats)",
                         "jit(_insert_step)"}
                assert steps <= set(names) <= steps | {
                    "jit(_threefry_fold_in)"}, names
                assert len(ev) <= 4, ev
            else:
                assert ev == [] and names == [], names
    finally:
        monitoring.unregister_event_duration_listener(named)
    assert co.cache_stats["misses"] == 6
