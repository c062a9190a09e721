"""prober.relayout_ms: device ms per call of the jitted
``estimate_batch_stats`` module spent in its ``copy`` operations
(``jit_estimate_batch_stats/copy.105`` and the like): relayouts inside
the probe, such as a copy of a corpus whose default layout the probe's
row gathers cannot read."""
import re

COPY = re.compile(r"estimate_batch_stats/copy")


def read(run):
    if run.trace is None:
        return None
    calls = run.trace.module_calls_ms(r"estimate_batch_stats")
    if not calls:
        return None
    ns = sum(t for name, t in run.trace.ops.items() if COPY.search(name))
    return ns * 1e-6 / len(calls)
