"""cache.hit_pct: estimate-cache hits over lookups in the window, from the
coalescer's own ``cache_stats`` counters."""


def read(run):
    n = run.cache_stats.get("lookups", 0)
    return 100.0 * run.cache_stats["hits"] / n if n else None
