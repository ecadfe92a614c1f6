"""executor: result-cache hits as a share of lookups over the window
(`result_cache_stats`, always on)."""


def read(spans, counters, trace, window):
    hits = counters.get("coalescer.cache_hits", 0)
    total = hits + counters.get("coalescer.cache_misses", 0)
    if not total:
        return None
    return 100.0 * hits / total
