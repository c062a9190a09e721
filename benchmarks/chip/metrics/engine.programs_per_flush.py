"""engine.programs_per_flush: mean number of programs that start on
device 0 inside a ``coal/flush`` span (the coalescer's own span around
each flush), over the traced part of the window. Reads a
``phases.PhaseSummary``; None from a trace without the launches."""
import numpy as np


def read(run):
    launches = getattr(run.trace, "launches_in", None)
    n = launches("coal/flush") if launches else []
    return float(np.mean(n)) if n else None
