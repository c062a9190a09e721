"""prober.visits: mean number of candidates a probe sampled
(``CardRequest.nvisited``), over the reads that were probed."""
import numpy as np


def read(run):
    return float(np.mean(run.nvisited)) if len(run.nvisited) else None
