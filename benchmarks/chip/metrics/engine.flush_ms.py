"""engine.flush_ms: mean wall time of the harness's ``bench/flush`` span,
around each ``CardinalityCoalescer.flush()`` of the traced window."""
import numpy as np


def read(run):
    if run.trace is None:
        return None
    d = run.trace.span_durations_ms("bench/flush")
    return float(np.mean(d)) if d else None
