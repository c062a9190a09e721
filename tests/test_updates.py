"""Capacity-padded dynamic updates (paper §5, DESIGN.md §10).

Covers: update-then-estimate equivalence vs a from-scratch build for the
in-capacity (recompile-free) and capacity-doubling paths, the
zero-new-compilations contract for in-capacity ingest, the capacity-padded
layout invariants, the jitted Alg. 9 neighbor-table step, the serve-layer
ingest path, and regressions for the serving-engine slot-position and
finished-request bugs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import compile_events

from repro.core import estimator as E, lsh, neighbors, updates
from repro.core.config import ProberConfig

CFG = ProberConfig(n_tables=2, n_funcs=6, ring_budget=512,
                   central_budget=512, chunk=128)
PQCFG = CFG.replace(use_pq=True, pq_m=4, pq_kc=16, pq_iters=4)


@pytest.fixture(scope="module")
def data():
    return jax.random.normal(jax.random.PRNGKey(0), (2048, 16))


def _stream(state, x_stream, cfg, chunk):
    for i in range(0, x_stream.shape[0], chunk):
        state = E.update(state, x_stream[i:i + chunk], cfg)
    return state


def _ests(st, cfg, qs, taus):
    return np.asarray(E.estimate_batch(st, qs, taus, cfg,
                                       jax.random.PRNGKey(7)))


@pytest.mark.parametrize("cfg", [CFG, PQCFG], ids=["exact", "pq"])
def test_incremental_equals_fresh_build_in_capacity(data, cfg):
    """K in-capacity updates ~ one build over the concatenated data."""
    key = jax.random.PRNGKey(0)
    n0, n = 1024, 1536
    st = E.build(data[:n0], cfg, key, capacity=4096)
    st = _stream(st, data[n0:n], cfg, chunk=128)
    assert int(st.n_valid) == n and st.capacity == 4096

    fresh = E.build(data[:n], cfg, key)
    qs = data[:6] + 0.01
    taus = jnp.linspace(3.0, 6.0, 6)
    got = _ests(st, cfg, qs, taus)
    want = _ests(fresh, cfg, qs, taus)
    truth = np.asarray([float(E.true_cardinality(data[:n], qs[i], taus[i]))
                        for i in range(6)])
    # same hash functions + exact Alg. 7 W renormalisation => the LSH layout
    # matches the fresh build; PQ centroids differ (incremental means), so
    # compare both paths against truth with matched tolerance
    ref = np.maximum(truth, 10.0)
    assert np.all(np.abs(got - truth) <= 1.0 * ref + 1e-6), (got, truth)
    assert np.all(np.abs(got - want) <= 0.75 * ref + 1e-6), (got, want)


def test_incremental_equals_fresh_build_through_doubling(data):
    """Growth path: stream past the initial capacity (several doublings)."""
    key = jax.random.PRNGKey(0)
    n0 = 512
    st = E.build(data[:n0], PQCFG, key, capacity=512)   # zero spare rows
    st = _stream(st, data[n0:], PQCFG, chunk=256)
    assert int(st.n_valid) == data.shape[0]
    assert st.capacity >= data.shape[0]

    fresh = E.build(data, PQCFG, key)
    qs = data[:5] + 0.01
    taus = jnp.linspace(3.0, 6.0, 5)
    got = _ests(st, PQCFG, qs, taus)
    truth = np.asarray([float(E.true_cardinality(data, qs[i], taus[i]))
                        for i in range(5)])
    want = _ests(fresh, PQCFG, qs, taus)
    ref = np.maximum(truth, 10.0)
    assert np.all(np.abs(got - truth) <= 1.0 * ref + 1e-6), (got, truth)
    assert np.all(np.abs(got - want) <= 0.75 * ref + 1e-6), (got, want)


def test_in_capacity_update_zero_new_compilations(data):
    """The recompile-free contract (DESIGN.md §10): once one in-capacity
    update of a given chunk shape has compiled, further updates (and the
    estimates between them) trigger ZERO new XLA compilations."""
    key = jax.random.PRNGKey(0)
    st = E.build(data[:1024], PQCFG, key, capacity=4096)
    q, tau = data[0] + 0.01, jnp.float32(4.0)
    E.estimate(st, q, tau, PQCFG, key)                    # warm estimate
    st = E.update(st, data[1024:1152], PQCFG)             # warm ingest @128
    E.estimate(st, q, tau, PQCFG, key)

    with compile_events() as ev:
        st = E.update(st, data[1152:1280], PQCFG)
        st = E.update(st, data[1280:1408], PQCFG)
        est = float(E.estimate(st, q, tau, PQCFG, key))
    assert ev == [], f"in-capacity update recompiled: {ev}"
    assert int(st.n_valid) == 1408
    assert 0.0 <= est <= 1408


def test_padded_build_matches_plain_build_estimates(data):
    """Capacity padding must not change results: a padded build estimates
    exactly like the same build without spare rows (same keys)."""
    key = jax.random.PRNGKey(1)
    qs = data[:4] + 0.01
    taus = jnp.linspace(3.0, 6.0, 4)
    plain = E.build(data[:1000], CFG, key)
    padded = E.build(data[:1000], CFG, key, capacity=3000)
    np.testing.assert_array_equal(_ests(plain, CFG, qs, taus),
                                  _ests(padded, CFG, qs, taus))


def test_padded_layout_invariants(data):
    """Sentinel bucket: live buckets partition exactly the live rows;
    padding rows sit past every live CSR entry."""
    idx = E.build(data[:1000], CFG, jax.random.PRNGKey(2),
                  capacity=2048).index
    assert int(idx.n_valid) == 1000
    for t in range(idx.n_tables):
        nb = int(idx.n_buckets[t])
        sizes = np.asarray(idx.bucket_sizes[t])
        starts = np.asarray(idx.bucket_starts[t])
        order = np.asarray(idx.order[t])
        assert sizes[:nb].sum() == 1000
        assert starts[0] == 0
        np.testing.assert_array_equal(starts[1:nb],
                                      np.cumsum(sizes[:nb])[:-1])
        # live CSR rows reference live points only; dead ids fill the tail
        assert sorted(order[:1000].tolist()) == list(range(1000))
        assert sorted(order[1000:].tolist()) == list(range(1000, 2048))
        # padding point codes are sentinel
        assert (np.asarray(idx.codes[t][1000:]) == lsh.CODE_SENTINEL).all()


def test_neighbor_update_jitted_fixed_shape():
    """Alg. 9 as a fixed-shape jitted step over capacity-padded codes."""
    key = jax.random.PRNGKey(3)
    old = np.unique(np.asarray(
        jax.random.randint(key, (30, 5), 0, 4)), axis=0)
    new = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (6, 5), 0, 4))
    n_old, n_all, cap = len(old), len(old) + len(new), 64
    codes_pad = np.full((cap, 5), lsh.CODE_SENTINEL, np.int32)
    codes_pad[:n_old] = old
    codes_pad[n_old:n_all] = new
    table = neighbors.build(jnp.asarray(codes_pad[:n_old]),
                            jnp.int32(n_old), max_dist=4)
    table = neighbors.grow(table, cap)
    step = jax.jit(neighbors.update)
    updated = step(table, jnp.asarray(codes_pad), jnp.int32(n_old),
                   jnp.int32(n_all))
    fresh = neighbors.build(jnp.asarray(codes_pad[:n_all]),
                            jnp.int32(n_all), max_dist=4)
    np.testing.assert_array_equal(
        np.asarray(updated.dists)[:n_all, :n_all],
        np.asarray(fresh.dists))
    # a second jitted call with in-capacity shapes adds no compilation
    with compile_events() as ev:
        step(updated, jnp.asarray(codes_pad), jnp.int32(n_all),
             jnp.int32(n_all))
    assert ev == []


def test_coalescer_ingest_interleaves_with_estimates(data):
    """Serve-layer ingest: estimates after ingest() see the new points."""
    from repro.serve.engine import CardinalityCoalescer
    cfg = CFG.replace(ingest_chunk=128)
    key = jax.random.PRNGKey(5)
    st = E.build(data[:1024], cfg, key, capacity=4096)
    co = CardinalityCoalescer(st, cfg, key, max_batch=8)
    # a point far from the initial corpus: cardinality ~0 before ingest
    far = data[0] + 50.0
    r0 = co.submit(np.asarray(far), 3.0)
    co.flush()
    assert r0.est is not None and r0.est < 1.0
    # ingest a cluster AT that location (> one chunk, with a partial tail)
    cluster = far[None, :] + 0.1 * np.asarray(
        jax.random.normal(jax.random.PRNGKey(6), (300, 16)))
    left = co.ingest(cluster)
    assert left < 128                       # full chunks applied eagerly
    r1 = co.submit(np.asarray(far), 3.0)
    co.flush()                              # drains the partial chunk first
    assert int(co.state.n_valid) == 1024 + 300
    assert r1.est > 100.0, r1.est           # the cluster is now visible


def _smoke_engine(batch_slots=2, max_len=48):
    from repro import configs
    from repro.models import get_family
    from repro.serve.engine import ServeEngine
    cfg = configs.get_smoke_config("qwen2-7b")
    fam = get_family(cfg)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    return ServeEngine(cfg, params, batch_slots=batch_slots, max_len=max_len)


def test_engine_per_slot_positions():
    """Regression: a slot admitted after a longer request must keep its own
    decode position, not inherit the max across slots."""
    from repro.serve.engine import Request
    eng = _smoke_engine()
    rng = np.random.default_rng(1)
    eng.submit(Request(rid=0, prompt=rng.integers(2, 50, size=20), max_new=6))
    eng.submit(Request(rid=1, prompt=rng.integers(2, 50, size=4), max_new=6))
    eng.step()
    pos = np.asarray(eng.cache["pos"])
    # after one decode step: prompt_len + 1 each, independently
    assert pos[0] == 21 and pos[1] == 5, pos
    done = eng.run()
    assert {r.rid for r in done} == {0, 1}
    assert all(len(r.out) == 6 for r in done)


def test_engine_short_slot_not_retired_by_long_neighbor():
    """Regression: the max_len retirement check must be per-slot — the long
    request hitting the cache ceiling used to retire every live slot."""
    from repro.serve.engine import Request
    eng = _smoke_engine(max_len=24)
    rng = np.random.default_rng(2)
    eng.submit(Request(rid=0, prompt=rng.integers(2, 50, size=20),
                       max_new=16))
    eng.submit(Request(rid=1, prompt=rng.integers(2, 50, size=3),
                       max_new=16))
    done = eng.run()
    by_rid = {r.rid: r for r in done}
    assert set(by_rid) == {0, 1}
    # slot 0 retires at the cache ceiling (24 - 21 = 3 decode steps); slot 1
    # has plenty of headroom and must reach its own max_new budget
    assert len(by_rid[0].out) < 16
    assert len(by_rid[1].out) == 16


def test_engine_run_returns_midrun_and_preadmitted_requests():
    """Regression: run() snapshotted the queue at entry, losing requests
    already admitted to slots and requests submitted while running."""
    from repro.serve.engine import Request
    eng = _smoke_engine()
    rng = np.random.default_rng(3)
    eng.submit(Request(rid=0, prompt=rng.integers(2, 50, size=4), max_new=3))
    eng.step()                    # rid 0 admitted to a slot, queue now empty
    eng.submit(Request(rid=1, prompt=rng.integers(2, 50, size=4), max_new=3))
    done = eng.run()
    assert {r.rid for r in done} == {0, 1}
    assert all(r.done for r in done)
    # nothing is returned twice
    assert eng.run(max_steps=4) == []


def test_exact_mode_is_exact_at_a_width_off_the_lanes():
    """At d = 120 the capacity-padded corpus is stored 128 columns wide.
    With every ring walked whole (no PQ, budgets above the corpus), the
    probe returns the brute-force count exactly, before and after one
    ingest through the coalescer, for queries sent at their own width."""
    from repro.data import vectors
    from repro.serve.engine import CardinalityCoalescer
    d, n0, n_new = 120, 1536, 256
    x = np.asarray(vectors.make_corpus(jax.random.PRNGKey(4), n0 + n_new, d))
    cfg = ProberConfig(n_tables=1, n_funcs=8, ring_budget=4096,
                       central_budget=4096, chunk=256, eps=0.0, s1=1.0,
                       max_visit=1 << 20, ingest_chunk=n_new)
    st = E.build(jnp.asarray(x[:n0]), cfg, jax.random.PRNGKey(0),
                 capacity=4096)
    assert st.x.shape == (4096, E.stored_width(d)) == (4096, 128)
    coal = CardinalityCoalescer(st, cfg, jax.random.PRNGKey(1), max_batch=8,
                                cache_size=64)
    qs = x[:8] + 0.01
    d2 = ((x[None, :, :] - qs[:, None, :]) ** 2).sum(-1)
    taus = np.sqrt(np.sort(d2[:, :n0], axis=1)[:, [0, 5, 40, 300, 900, 1,
                                                   20, 700]].diagonal())
    for n_live in (n0, n0 + n_new):
        if n_live > n0:
            coal.ingest(x[n0:])
        reqs = [coal.submit(q, t * 1.0001) for q, t in zip(qs, taus)]
        res = coal.flush()
        want = (d2[:, :n_live] <= (taus[:, None] * 1.0001) ** 2).sum(1)
        np.testing.assert_array_equal([res[r.rid] for r in reqs], want)
        assert all(res[r.rid].provenance != "hit" for r in reqs)
    assert int(coal.state.n_valid) == n0 + n_new


@pytest.mark.parametrize("int8_lut", [False, True])
def test_queries_at_the_stored_width_give_the_same_estimates(int8_lut):
    """A query may come as wide as the stored corpus, zero past d (the
    probe step is lowered so for its compiled text): at d = 120 with PQ
    rings, the estimates, rings and visits equal those of the same
    queries at d."""
    from repro.data import vectors
    d = 120
    x = vectors.make_corpus(jax.random.PRNGKey(5), 3000, d)
    cfg = ProberConfig(n_tables=2, n_funcs=8, chunk=128, use_pq=True,
                       pq_m=8, pq_kc=16, pq_iters=4, pq_int8_lut=int8_lut,
                       central_budget=64)
    st = E.build(x, cfg, jax.random.PRNGKey(0), capacity=4096)
    qs = x[:8] + 0.01
    taus = jnp.linspace(0.5, 3.0, 8)
    wide = jnp.pad(qs, ((0, 0), (0, st.x.shape[1] - d)))
    key = jax.random.PRNGKey(2)
    at_d = E.estimate_batch_stats(st, qs, taus, cfg, key)
    at_stored = E.estimate_batch_stats(st, wide, taus, cfg, key)
    for a, b in zip(at_d, at_stored):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
