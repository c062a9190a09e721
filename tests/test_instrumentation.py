"""The coalescer's spans and counters, and the prober's device scopes.

What they promise: the ``stats`` counters count the work a flush does
(batches stepped, device-to-host reads, lanes sent to the prober); the
jitted probe step's HLO carries the ``probe/*`` scopes in its ``op_name``
metadata; and recording a profile changes no estimate.
"""
import pathlib
import re
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import estimator as E
from repro.core.config import ProberConfig
from repro.core.updates import next_pow2
from repro.serve.engine import CardinalityCoalescer

CFG = ProberConfig(n_tables=2, n_funcs=6, ring_budget=512,
                   central_budget=512, chunk=128)
PQ = ProberConfig(n_tables=1, n_funcs=6, ring_budget=512,
                  central_budget=512, chunk=128, use_pq=True, pq_m=4,
                  pq_kc=16, pq_int8_lut=True, pq_exact_central=True)


@pytest.fixture(scope="module")
def data():
    return np.asarray(jax.random.normal(jax.random.PRNGKey(0), (2048, 16)))


def _coalescer(data, cfg=CFG, cache_size=64, max_batch=8):
    key = jax.random.PRNGKey(0)
    st = E.build(jnp.asarray(data[:1024]), cfg, key, capacity=4096,
                 track_epochs=cache_size > 0)
    return CardinalityCoalescer(st, cfg, key, max_batch=max_batch,
                                cache_size=cache_size)


def _delta(co, before):
    return {k: v - before[k] for k, v in co.stats.items()}


def test_syncs_per_cached_flush(data):
    """An all-miss flush makes two reads: the lookup's (hit, stale,
    cached estimate) together, then the merge's (evictions, estimates,
    rings, visits) together. An all-hit flush makes only the lookup's."""
    co = _coalescer(data)
    qs = [data[i] + 0.01 for i in range(5)]
    before = dict(co.stats)
    for q in qs:
        co.submit(q, 4.0)
    co.flush()
    d = _delta(co, before)
    assert (d["flushes"], d["syncs"]) == (1, 2)
    before = dict(co.stats)
    for q in qs:
        co.submit(q, 4.0)
    co.flush()
    d = _delta(co, before)
    assert co.cache_stats["hits"] == 5
    assert (d["flushes"], d["syncs"], d["probe_lanes"]) == (1, 1, 0)


@pytest.mark.parametrize("n_hit,n_miss", [(0, 3), (2, 3), (1, 5), (4, 1)])
def test_probe_lanes_pad_the_misses(data, n_hit, n_miss):
    """Only the misses go to the prober, padded to the next power of two:
    ``probe_lanes`` counts the padding, ``probe_live`` the misses."""
    co = _coalescer(data)
    for i in range(n_hit):
        co.submit(data[i] + 0.01, 4.0)
    co.flush()
    before = dict(co.stats)
    for i in range(n_hit + n_miss):
        co.submit(data[i] + 0.01, 4.0)
    co.flush()
    d = _delta(co, before)
    assert d["probe_live"] == n_miss
    assert d["probe_lanes"] == next_pow2(n_miss)


def test_uncached_flush_counts(data):
    """Without the cache every lane is probed, padding included, and the
    estimates are the one read back."""
    co = _coalescer(data, cache_size=0)
    for i in range(3):
        co.submit(data[i], 4.0)
    co.flush()
    assert co.stats["flushes"] == 1 and co.stats["syncs"] == 1
    assert (co.stats["probe_lanes"], co.stats["probe_live"]) == (4, 3)


def test_flushes_count_batches(data):
    """``flushes`` counts the batches stepped, an auto-flush at
    ``max_batch`` included; a flush with nothing pending steps none."""
    co = _coalescer(data, max_batch=4)
    for i in range(6):                     # 4 auto-flushed, 2 left
        co.submit(data[i], 4.0)
    assert co.stats["flushes"] == 1
    co.flush()
    co.flush()
    assert co.stats["flushes"] == 2


@pytest.mark.parametrize("cfg", [CFG, PQ], ids=["exact", "pq"])
def test_probe_scopes_in_hlo(data, cfg):
    """The served probe step's compiled HLO names its phases in the
    ``op_name`` metadata of its instructions."""
    st = E.build(jnp.asarray(data[:1024]), cfg, jax.random.PRNGKey(1),
                 capacity=2048)
    text = E.estimate_batch_stats.lower(
        st, jnp.zeros((4, 16)), jnp.ones((4,)), cfg,
        jax.random.PRNGKey(2)).compile().as_text()
    names = " ".join(re.findall(r'op_name="([^"]*)"', text))
    for scope in ("probe/prep", "probe/rings", "probe/central",
                  "probe/slab"):
        assert scope in names, scope


def test_profile_changes_no_estimate(data):
    """The same requests through two identical coalescers, one inside a
    recorded profile, get bit-identical estimates; the profile holds the
    coalescer's spans."""
    from jax.profiler import ProfileData
    plain, traced = _coalescer(data), _coalescer(data)
    reqs = {}
    with tempfile.TemporaryDirectory() as d:
        for co, on in ((plain, False), (traced, True)):
            if on:
                jax.profiler.start_trace(d)
            rs = [co.submit(data[i] + 0.02, 3.0 + i % 3) for i in range(11)]
            co.flush()
            if on:
                jax.profiler.stop_trace()
            reqs[on] = rs
        path = sorted(pathlib.Path(d).rglob("*.xplane.pb"))[-1]
        names = {ev.name for plane in ProfileData.from_file(str(path)).planes
                 for line in plane.lines for ev in line.events}
    assert [r.est for r in reqs[False]] == [r.est for r in reqs[True]]
    assert {"coal/flush", "coal/pack", "coal/lookup", "coal/probe",
            "coal/insert", "coal/merge", "coal/sync"} <= names
