"""prober.device_ms: mean device time of one call of the jitted
``estimate_batch_stats`` module (the probe of a flush's cache misses)."""
import numpy as np


def read(run):
    if run.trace is None:
        return None
    d = run.trace.module_calls_ms(r"estimate_batch_stats")
    return float(np.mean(d)) if d else None
