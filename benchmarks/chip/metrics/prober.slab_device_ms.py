"""prober.slab_device_ms: device ms per call of ``estimate_batch_stats``
under the named scope ``probe/slab``: the progressive-sampling loop (row
and code gathers, distances, the stopping test). Reads a
``phases.PhaseSummary``; None from a trace without scopes."""


def read(run):
    split = getattr(run.trace, "scope_ms", None)
    return split(r"estimate_batch_stats").get("slab") if split else None
