"""engine.host_pct: share of the time inside ``bench/flush`` spans in
which no operation ran on the device: the coalescer's host work."""


def read(run):
    if run.trace is None:
        return None
    s = run.trace.host_share("bench/flush")
    return None if s is None else 100.0 * s
