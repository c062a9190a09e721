"""Workload generator smoke tests (DESIGN.md §12) — seeded, tiny N.

Tier-1 guards for benchmarks/workloads.py: determinism (same seed, same
stream), schema, zipfian head concentration, drift non-stationarity, tau
band correlation, and the mixed stream's ingest events — plus a micro
end-to-end run of the bench harness's serve loop so the cache
partition/merge step can't regress silently outside CI's bench smoke.
"""
import numpy as np
import pytest

from benchmarks import workloads


@pytest.fixture(scope="module")
def ds():
    from repro.data import vectors
    return vectors.load("sift", n_queries=4, scale=0.02)   # 800 x 128


def test_default_corpus_seed_is_stable(ds):
    """A keyless load draws the same corpus in every process: its seed is
    a fixed function of the name, not of PYTHONHASHSEED."""
    import jax
    from repro.data import vectors
    assert vectors.default_seed("sift") == 2064079301
    again = vectors.load("sift", key=jax.random.PRNGKey(2064079301),
                         n_queries=4, scale=0.02)
    np.testing.assert_array_equal(np.asarray(ds.x), np.asarray(again.x))


def test_streams_deterministic(ds):
    a = workloads.generate(ds, "zipf", n_events=64, pool=16, seed=7)
    b = workloads.generate(ds, "zipf", n_events=64, pool=16, seed=7)
    assert a.events == b.events
    np.testing.assert_array_equal(a.taus, b.taus)
    c = workloads.generate(ds, "zipf", n_events=64, pool=16, seed=8)
    assert c.events != a.events


def test_schema_and_truth(ds):
    wl = workloads.generate(ds, "zipf", n_events=64, pool=16, seed=0)
    assert wl.n_queries == 64
    for kind, payload in wl.events:
        assert kind == "q"
        q, tau, truth = wl.request(payload)
        assert q.shape == (ds.x.shape[1],) and tau > 0 and truth >= 0
    # truth matches the dataset's exact grid cardinalities
    d2 = np.sum((np.asarray(ds.x) - wl.qs[0]) ** 2, axis=-1)
    assert np.sum(d2 <= wl.taus[0] ** 2) == wl.truth[0]


def test_zipf_head_concentration(ds):
    wl = workloads.generate(ds, "zipf", n_events=512, pool=32, skew=0.99,
                            seed=0)
    counts = np.bincount([p for _, p in wl.events], minlength=32)
    # the head must dominate a uniform draw (512/32 = 16 per key)
    assert counts.max() > 4 * 512 / 32
    assert (counts > 0).sum() < 32                 # and the tail is thin


def test_drift_changes_popular_set(ds):
    wl = workloads.generate(ds, "drift", n_events=512, pool=48, seed=0,
                            phase_len=128)
    early = {p for _, p in wl.events[:128]}
    late = {p for _, p in wl.events[-128:]}
    assert late - early, "popularity window never moved"


def test_tau_corr_bands_per_query(ds):
    wl = workloads.generate(ds, "tau-corr", n_events=256, pool=8, seed=0,
                            tau_band=2)
    by_query: dict = {}
    for _, p in wl.events:
        by_query.setdefault(wl.qs[p].tobytes(), set()).add(float(wl.taus[p]))
    assert by_query, "no events"
    assert all(1 <= len(ts) <= 2 for ts in by_query.values()), \
        "a client wandered outside its tau band"


def test_mixed_stream_has_ingests(ds):
    wl = workloads.generate(ds, "mixed", n_events=128, pool=16, seed=0,
                            ingest_every=32, ingest_n=8)
    kinds = [k for k, _ in wl.events]
    assert kinds.count("ingest") == 3              # t = 32, 64, 96
    for kind, payload in wl.events:
        if kind == "ingest":
            assert payload.shape == (8, ds.x.shape[1])
            assert payload.dtype == np.float32


def test_harness_micro_end_to_end(ds):
    """The bench harness's serve loop over a tiny mixed stream: hits
    appear, stale refreshes appear after ingest, nothing crashes, and the
    cached side's estimates for exact repeats match the fresh-probe values
    recorded at insert time."""
    import jax

    from benchmarks import bench_latency
    from repro.core import estimator as E, updates as U
    from repro.core.config import ProberConfig
    from repro.serve.engine import CardinalityCoalescer

    cfg = ProberConfig(n_tables=1, n_funcs=8, ring_budget=256,
                       central_budget=256, chunk=128, max_visit=512,
                       ingest_chunk=64)
    wl = workloads.generate(ds, "mixed", n_events=48, pool=8, seed=0,
                            ingest_every=16, ingest_n=8)
    n = ds.x.shape[0]
    n_ingest = sum(e[1].shape[0] for e in wl.events if e[0] == "ingest")
    state = E.build(ds.x, cfg, jax.random.PRNGKey(0), track_epochs=True,
                    capacity=U.next_capacity(n, n + n_ingest))
    co = CardinalityCoalescer(state, cfg, jax.random.PRNGKey(0),
                              max_batch=8, cache_size=32)
    qps, served = bench_latency._serve_workload(wl, co, batch=8)
    assert qps > 0 and len(served) == wl.n_queries
    assert co.cache_stats["hits"] > 0
    assert co.cache_stats["lookups"] == wl.n_queries
    first_serve: dict = {}
    for pi, req in served:
        if req.provenance == "hit":
            assert req.est == first_serve[pi]      # replays, bit-identical
        else:
            first_serve[pi] = req.est
