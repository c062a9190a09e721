"""The trace reduction: on hand-made intervals, and on a small trace
recorded on a TPU v5e (testdata/)."""
import gzip
import pathlib
import shutil

import numpy as np
import pytest

from benchmarks.chip import trace

# three flushes of 8 reads and three 32-point ingests through the
# coalescer on one v5e chip, inside bench/* spans
SAMPLE = pathlib.Path(__file__).parent / "testdata" / \
    "v5e_flushes.xplane.pb.gz"


def test_union_busy_idle_and_gap_attribution():
    busy = trace._union(np.array([[10, 20], [15, 30], [50, 60], [58, 70]],
                                 np.float64))
    np.testing.assert_array_equal(busy, [[10, 30], [50, 70]])
    spans = [("bench/window", 0, 100, 0), ("bench/flush", 5, 40, 1),
             ("bench/sleep", 40, 50, 1), ("bench/flush", 50, 100, 1)]
    s = trace.TraceSummary((0, 100), [busy], {"fusion": 40.0},
                           {"jit_a": [5.0, 7.0]}, spans)
    assert s.window_s == pytest.approx(100e-9)
    assert s.busy_s == pytest.approx(40e-9)
    gaps = s.idle_gaps()        # [0,10), [30,50), [70,100), longest first
    assert [round(g[1] * 1e9) for g in gaps] == [30, 20, 10]
    assert [g[0] for g in gaps] == ["bench/flush", "bench/sleep",
                                    "bench/flush"]
    # flush spans cover 85 ns, of which 40 busy
    assert s.host_share("bench/flush") == pytest.approx(1 - 40 / 85)
    assert s.module_calls_ms("jit_a") == pytest.approx([5e-6, 7e-6])
    b = s.breakdown()
    assert b["device_ops"] == [["fusion", 40e-9]]
    assert len(b["idle_gaps"]) == 3


def test_recorded_v5e_trace(tmp_path):
    path = tmp_path / "sample.xplane.pb"
    with gzip.open(SAMPLE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    s = trace.summarize(path)
    assert 0 < s.busy_s < s.window_s
    assert s.module_calls_ms(r"estimate_batch_stats")
    assert s.module_calls_ms(r"_ingest_core")
    share = s.host_share("bench/flush")
    assert 0 <= share <= 1
    names = {n for n, _ in s.idle_gaps()}
    assert names <= {"bench/flush", "bench/submit", "bench/sleep", "none"}
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    # ops are named by module and instruction, not by their HLO text
    assert all(" = " not in n for n, _ in b["device_ops"])
    assert any(n.startswith("jit_estimate_batch_stats/")
               for n, _ in b["device_ops"])
