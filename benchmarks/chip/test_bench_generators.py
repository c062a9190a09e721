"""The corpus, query-grid and traffic generators repeat exactly by seed."""
import jax
import numpy as np
import pytest

from benchmarks.chip import corpus, harness, traffic

BIG_SEED = 2 ** 40 + 3          # past 32 signed bits


def test_corpus_and_grid_repeat_by_seed():
    k, shape = harness.seed_key(BIG_SEED), jax.random.PRNGKey(7)
    a = corpus.make_corpus(shape, k, 2000, 16)
    b = corpus.make_corpus(shape, harness.seed_key(BIG_SEED), 2000, 16)
    c = corpus.make_corpus(shape, harness.seed_key(BIG_SEED + 1), 2000, 16)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(c))
    tgt = corpus.targets(20, 12)
    r1, q1, t1 = corpus.query_grid(k, a, 5, tgt)
    r2, q2, t2 = corpus.query_grid(k, b, 5, tgt)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    # each radius reaches exactly its target count (distinct distances)
    x = np.asarray(a, np.float64)
    for q, taus in zip(np.asarray(q1, np.float64), np.asarray(t1)):
        d2 = ((x - q) ** 2).sum(-1)
        np.testing.assert_array_equal((d2[None] <= taus[:, None] ** 2)
                                      .sum(-1), tgt)


MIXES = {
    "distinct": {"reads": {"rate_per_s": 50, "order": "distinct"}},
    "zipf": {"reads": {"rate_per_s": 50, "order": "zipf",
                       "pool_pairs": 100, "skew": 0.99},
             "ingest": {"batch_points": 4, "batches_per_s": 8, "noise": 0.05,
                        "warmup_batches": [4, 8]}},
}


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_schedule_repeats_by_seed(mix):
    make = lambda s: traffic.make_schedule(MIXES[mix], s, 10, 1000, 6, 12)
    a, b, c = make(BIG_SEED), make(BIG_SEED), make(BIG_SEED + 1)
    for f in ("read_t", "read_pair", "pair_query", "pair_target",
              "ingest_t", "ingest_rows", "ingest_noise"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert not np.array_equal(a.read_t, c.read_t)
    # every seed offers the same amount of work in the window
    assert len(a.read_t) == len(c.read_t) == 500
    assert np.all(np.diff(a.read_t) > 0) and a.read_t[-1] < 10
    # ... with the same set of gaps, in another order
    ga, gc = np.sort(np.diff(a.read_t)), np.sort(np.diff(c.read_t))
    assert (np.abs(ga[:, None] - gc[None]).min(1) < 1e-9).mean() > 0.99
    if mix == "distinct":
        pairs = a.pair_query[a.read_pair] * 12 + a.pair_target[a.read_pair]
        assert len(set(pairs.tolist())) == len(pairs)
        assert a.pool_queries * 12 >= len(a.read_t)
    else:
        assert a.read_pair.max() < 100
        assert a.ingest_noise.shape == (80, 4, 6)
        assert [len(r) for r in a.warm_rows] == [4, 8]


def test_zipf_pool_is_skewed():
    s = traffic.make_schedule(MIXES["zipf"], 3, 200, 1000, 6, 12)
    counts = np.bincount(s.read_pair, minlength=100)
    assert counts[0] > 10 * np.median(counts)


def test_bursts_fall_in_the_on_periods():
    mix = {"reads": {"rate_per_s": 50, "order": "distinct",
                     "burst": {"on_s": 0.5, "off_s": 1.5}}}
    s = traffic.make_schedule(mix, BIG_SEED, 10, 1000, 6, 12)
    assert len(s.read_t) == 500 and s.outstanding is None
    assert np.all(np.diff(s.read_t) >= 0) and s.read_t[-1] < 10
    assert np.all(np.mod(s.read_t, 2.0) < 0.5)


def test_closed_loop_reads_are_due_on_admission():
    mix = {"reads": {"rate_per_s": 50, "order": "distinct", "outstanding": 8}}
    s = traffic.make_schedule(mix, BIG_SEED, 10, 1000, 6, 12)
    assert s.outstanding == 8 and len(s.read_t) == 500
    assert not s.read_t.any()
