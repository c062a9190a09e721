"""With the timed path broken underneath, a run comes out not correct:
once for each fault a one-chip estimate service can have (the run as
run.py drives it, at a tiny size, without the chip check). There is no
exchange between chips to leave out: every cell runs on one chip."""
import pytest

from repro.core import estimator as E
from repro.serve.engine import CardinalityCoalescer
from benchmarks.chip.tiny import WRITES, tiny_run

FAST = 400.0       # reads/s: flushes of several reads on the CPU


def _unchanged_state(state, x_new, cfg, n_valid=None):
    """An ingest step that returns its state unchanged."""
    return state


def _half_batch(real):
    """Half of each flush left out: its answers never come back."""
    def flush(self):
        out = real(self)
        keep = sorted(out)[: (len(out) + 1) // 2]
        return {rid: out[rid] for rid in keep}
    return flush


def _altered(real, lanes):
    """Estimates altered where they are produced: a hundred times too
    large in the first ``lanes`` lanes of each flush (None: every lane)."""
    def fake(*a, **k):
        est, pk, nv = real(*a, **k)
        return est.at[:lanes].multiply(100.0), pk, nv
    return fake


@pytest.mark.parametrize("fault,check", [
    ("unchanged_state", "lost_points"),
    ("half_batch", "unanswered"),
    ("altered", "qerror_gmean"),
    ("altered_one_lane", "qerror_p90"),
])
def test_fault_makes_run_not_correct(monkeypatch, fault, check):
    if fault == "unchanged_state":
        monkeypatch.setattr(E, "update", _unchanged_state)
    elif fault == "half_batch":
        monkeypatch.setattr(CardinalityCoalescer, "flush",
                            _half_batch(CardinalityCoalescer.flush))
    else:
        monkeypatch.setattr(E, "estimate_batch_stats",
                            _altered(E.estimate_batch_stats,
                                     1 if fault == "altered_one_lane"
                                     else None))
    # the state fault needs writes: the tiny mix with a write stream
    traffic = WRITES if fault == "unchanged_state" else None
    out = tiny_run("sift1m.uniform", rate=FAST, traffic=traffic,
                   drain_s=1.0)
    assert not out["correct"]
    c = out["checks"][check]
    assert c["value"] > c["limit"], out["checks"]
