"""engine.syncs_per_flush: blocking device-to-host reads per flush, from
the coalescer's ``stats`` counters (``syncs`` / ``flushes``) over the
traced part of the window (``run.counters``); None without them."""


def read(run):
    c = getattr(run, "counters", None) or {}
    return c["syncs"] / c["flushes"] if c.get("flushes") else None
