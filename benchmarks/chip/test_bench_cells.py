"""Every cell of BENCHMARK.json runs through the harness at a tiny size on
the CPU, answers every read and comes out correct."""
import numpy as np
import pytest

from benchmarks.chip import harness
from benchmarks.chip.tiny import (TINY_RATE, TINY_SECONDS, WRITES, bench,
                                  tiny_config, tiny_run)

CELLS = [w["name"] for w in bench()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_is_correct_at_tiny_size(cell):
    out = tiny_run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] == TINY_RATE * TINY_SECONDS
    assert out["failed"] == 0
    want = {m["name"] for m in harness.cell_metrics(bench(), cell,
                                                     "end_to_end")}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}


def test_traced_run_reports_per_layer_metrics():
    out = tiny_run("sift1m.uniform", trace=True)
    assert out["correct"], out["checks"]
    allowed = {m["name"] for m in harness.cell_metrics(
        bench(), "sift1m.uniform", "per_layer")}
    # the CPU trace has no device plane: device-time metrics are absent
    assert {"engine.flush_ms", "cache.hit_pct", "prober.visits"} <= \
        set(out["metrics"]) <= allowed
    assert out["device"]["window_s"] >= 0.95 * TINY_SECONDS
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def test_trace_records_the_tail_of_the_window(monkeypatch):
    """A traced run profiles only the last ``TRACE_SECONDS`` of a window
    that is longer, and still checks every read of the window."""
    from benchmarks.chip import trace
    monkeypatch.setattr(trace, "TRACE_SECONDS", 1.0)
    out = tiny_run("sift1m.uniform", trace=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] == TINY_RATE * TINY_SECONDS
    assert 0.9 <= out["device"]["window_s"] <= 1.5


def test_live_writes_are_visible_and_kept():
    """Reads served while points stream in: each answer is checked against
    the points handed in before its flush, and none is lost."""
    out = tiny_run("sift1m.uniform", traffic=WRITES)
    assert out["correct"], out["checks"]
    assert out["checks"]["lost_points"]["value"] == 0
    assert out["window"]["cache"]["stale"] > 0   # writes invalidated entries


def test_every_seed_serves_the_same_work():
    """The corpus, query pool and index come from the configuration's
    ``data_seed``; two run seeds offer the same reads in another order."""
    from benchmarks.chip import run
    config = tiny_config(bench()["workloads"][0]["config"])
    _, mix = run.load_cell(bench(), bench()["workloads"][0]["name"])
    a, b = (harness.Cell(config, mix, seed, TINY_SECONDS, lambda s: None)
            for seed in (2 ** 33 + 1, 2 ** 33 + 2))
    np.testing.assert_array_equal(np.asarray(a.corpus()),
                                  np.asarray(b.corpus()))
    np.testing.assert_array_equal(a.pool_tau, b.pool_tau)
    reads = lambda c: c.sched.pair_query[c.sched.read_pair] * 100 + \
        c.sched.pair_target[c.sched.read_pair]
    assert not np.array_equal(reads(a), reads(b))
    np.testing.assert_array_equal(np.sort(reads(a)), np.sort(reads(b)))
    # the same gaps, the last one running to the window's close
    gaps = lambda c: np.sort(np.diff(c.sched.read_t,
                                     append=TINY_SECONDS))
    np.testing.assert_allclose(gaps(a), gaps(b), atol=1e-9)
