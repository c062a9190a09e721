"""The lower-precision control comes out not correct: the program's own
code path (``run.py --control adc``: every distance from the 8-bit PQ
codes) in place of the float32 distances the configuration states for
the central bucket and the near rings, judged by the configuration's
limits. On the chip it runs at the cell's own size (PERF.md); here at
262,144 x 128 rows, a size at which the code path's false positives
already show, over 600 reads (50 queries of the grid)."""
from benchmarks.chip import run
from benchmarks.chip.tiny import bench, tiny, tiny_run

SIZE = {"corpus": {"n": 262144, "d": 128}, "serving": {"capacity": 524288},
        "prober": {"pq_m": 32}}


def test_adc_control_is_not_correct():
    config, _ = run.load_cell(bench(), "sift1m.uniform", control="adc")
    out = tiny_run("sift1m.uniform", config=tiny({**config, "tiny": SIZE}),
                   rate=300.0)
    over = [k for k, c in out["checks"].items() if c["value"] > c["limit"]]
    assert not out["correct"] and over, out["checks"]
    assert set(over) <= {"qerror_gmean", "qerror_p90"}
