"""mesh: the busiest chip's busy seconds in the traced slice over the
mean of the chips': 1.0 when the shards share the work evenly."""

from benchmark.harness import mesh_trace


def read(spans, counters, trace, window):
    busy = [mesh_trace.busy_seconds(p, window)
            for p in mesh_trace.planes(trace)]
    if len(busy) < 2 or sum(busy) <= 0:
        return None
    return max(busy) * len(busy) / sum(busy)
