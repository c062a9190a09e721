"""prober.rings_device_ms: device ms per call of ``estimate_batch_stats``
under the named scope ``probe/rings``: the Hamming compare, the ring size
cumsums and the sampling schedule. Reads a ``phases.PhaseSummary``; None
from a trace without scopes."""


def read(run):
    split = getattr(run.trace, "scope_ms", None)
    return split(r"estimate_batch_stats").get("rings") if split else None
