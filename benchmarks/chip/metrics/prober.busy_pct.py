"""prober.busy_pct: share of the traced window that the device spends in
calls of the jitted ``estimate_batch_stats`` module (the probe of a
flush's cache misses): how far the probe sets the cell's pace."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    calls = run.trace.module_calls_ms(r"estimate_batch_stats")
    if not calls:
        return None
    return 100.0 * sum(calls) * 1e-3 / run.trace.window_s
