"""mesh: how close the collectives come to the interconnect roofline.
The least bytes the queries of the traced slice must send between chips
under row partitioning (`ici_model`) at one chip's interconnect peak
(`ici_peaks.json`) are chip-seconds; so are the collective operations'
device seconds summed over the planes.  Latency-bound by nature here:
KB-sized tables and 4-byte reductions."""

from benchmark.harness import ici_model, mesh_trace


def read(spans, counters, trace, window):
    planes = mesh_trace.planes(trace)
    seconds = sum(mesh_trace.collective_seconds(p) for p in planes)
    if len(planes) < 2 or seconds <= 0:
        return None
    peak = mesh_trace.peak(window, "ici_peaks.json", "ici_bytes_per_s")
    moved = sum(
        ici_model.query_bytes(shape, rows, window["store"], len(planes))
        for shape, per_query in window["rows_by_shape_in_slice"].items()
        for rows in per_query)
    return 100.0 * (moved / peak) / seconds
