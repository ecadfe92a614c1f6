"""mesh: share of a chip's busy time spent in collective operations
(all-gather, all-reduce, all-to-all, collective-permute and their
start / done halves): collective seconds over busy seconds of the traced
slice, mean of the device planes."""

from benchmark.harness import mesh_trace


def read(spans, counters, trace, window):
    shares = []
    for plane in mesh_trace.planes(trace):
        busy = mesh_trace.busy_seconds(plane, window)
        if busy > 0:
            shares.append(mesh_trace.collective_seconds(plane) / busy)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
