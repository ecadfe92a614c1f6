"""storage: result-cache invalidation events per commit of the window
(`result_cache_stats`, always on) — what a commit costs the readers."""


def read(spans, counters, trace, window):
    if not window["commits"]:
        return None
    return counters.get("coalescer.cache_invalidations", 0) / window["commits"]
