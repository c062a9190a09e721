"""The probe's share of the window and its relayout copies, read from a
trace summary: hand-made, and the small v5e trace in testdata/."""
import gzip
import shutil

import numpy as np
import pytest

from benchmarks.chip import harness, trace
from benchmarks.chip.test_bench_trace import SAMPLE


def _run(summary):
    return harness.Run(seconds=1.0, setup_s=0.0, due=np.zeros(0),
                       done=np.zeros(0), est=np.zeros(0), prov=[],
                       nvisited=[], trace=summary)


def test_relayout_and_busy_on_hand_made_summary():
    ms = 1e6                                        # ns
    s = trace.TraceSummary(
        (0, 1000 * ms), [np.zeros((0, 2))],
        {"jit_estimate_batch_stats/copy.105": 24 * ms,
         "jit_estimate_batch_stats/fusion.164": 40 * ms,
         "jit__insert_step/copy.3": 5 * ms},
        {"jit_estimate_batch_stats(7f1)": [20 * ms, 20 * ms, 20 * ms],
         "jit__insert_step(2a)": [5 * ms]}, [])
    relayout = harness.load_metric("prober.relayout_ms").read(_run(s))
    busy = harness.load_metric("prober.busy_pct").read(_run(s))
    assert relayout == pytest.approx(8.0)           # 24 ms over 3 calls
    assert busy == pytest.approx(6.0)               # 60 ms of 1 s
    empty = trace.TraceSummary((0, 1000 * ms), [], {}, {}, [])
    for name in ("prober.relayout_ms", "prober.busy_pct"):
        assert harness.load_metric(name).read(_run(empty)) is None
        assert harness.load_metric(name).read(_run(None)) is None


def test_relayout_and_busy_on_recorded_v5e_trace(tmp_path):
    """On the sample's three probes (sift-shaped, an earlier program) the
    copies are the rings' relayouts of bucket-axis arrays, a part of each
    call; the probe's busy share lies inside the device's."""
    path = tmp_path / "sample.xplane.pb"
    with gzip.open(SAMPLE) as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    s = trace.summarize(path)
    relayout = harness.load_metric("prober.relayout_ms").read(_run(s))
    busy = harness.load_metric("prober.busy_pct").read(_run(s))
    calls = s.module_calls_ms(r"estimate_batch_stats")
    copies = [t for n, t in s.ops.items()
              if n.startswith("jit_estimate_batch_stats/copy")]
    assert relayout == pytest.approx(sum(copies) * 1e-6 / len(calls))
    assert 0 < relayout < np.mean(calls)
    assert 0 < busy <= 100 * s.busy_s / s.window_s + 1e-9
