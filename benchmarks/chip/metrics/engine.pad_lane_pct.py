"""engine.pad_lane_pct: share of the lanes a flush sends to the prober
that are padding up to a power of two, from the coalescer's ``stats``
counters: 100 x (1 - ``probe_live`` / ``probe_lanes``) over the traced
part of the window (``run.counters``); None without them."""


def read(run):
    c = getattr(run, "counters", None) or {}
    if not c.get("probe_lanes"):
        return None
    return 100.0 * (1.0 - c["probe_live"] / c["probe_lanes"])
