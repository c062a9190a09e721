"""prober.central_device_ms: device ms per call of ``estimate_batch_stats``
under the named scope ``probe/central``: the exact count of the central
bucket. Reads a ``phases.PhaseSummary``; None from a trace without
scopes."""


def read(run):
    split = getattr(run.trace, "scope_ms", None)
    return split(r"estimate_batch_stats").get("central") if split else None
