"""coalescer: queries per drained batch over the window
(`coalescer_stats()` items / batches, always on)."""


def read(spans, counters, trace, window):
    batches = counters.get("coalescer.batches", 0)
    if not batches:
        return None
    return counters.get("coalescer.items", 0) / batches
