"""The write stream on gist1m's tiny configuration (d = 120, stored 128
wide) runs through the harness on the CPU and comes out correct."""
from benchmarks.chip.tiny import WRITES, tiny_run


def test_live_writes_through_the_widened_corpus():
    """Ingested rows are widened with the corpus, every answer is checked
    against the points handed in before it, and none is lost."""
    out = tiny_run("gist1m.uniform", traffic=WRITES)
    assert out["correct"], out["checks"]
    assert out["checks"]["lost_points"]["value"] == 0
    assert out["window"]["cache"]["stale"] > 0
