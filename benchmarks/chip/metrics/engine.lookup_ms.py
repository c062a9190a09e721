"""engine.lookup_ms: mean wall time of the coalescer's ``coal/lookup`` span:
hashing the flush, its key bands and live mask (eager programs on the
host), the estimate-cache lookup and the reads of its answers. None from
a trace without ``coal/*`` spans."""
import numpy as np


def read(run):
    if run.trace is None:
        return None
    d = run.trace.span_durations_ms("coal/lookup")
    return float(np.mean(d)) if d else None
