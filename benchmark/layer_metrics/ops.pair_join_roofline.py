"""ops: how close the verified join comes to the HBM roofline.  The
bytes it must move for every query program of the traced slice
(`pair_join_model`: left rows once, the right table once, kept rows
once; a program cut by the slice's edge counts by the share of it
inside) at the device's peak bandwidth (`peaks.json`), as a share of the
device time under the scope `join.pair_verify` in the slice.  The work
is counted per PROGRAM run in the slice, whatever number of requests a
program answered (on the cell a request is mostly a batch and a program
of its own).  Can only pass 100 % if the scope leaves part of the
join's work out."""

from benchmark.harness import (devtrace, mesh_trace, pair_join_model, readers,
                               scope_trace)


def programs_in_slice(trace, window) -> float:
    """Query programs run in the slice, one cut by its edge as the
    share of its duration that lies inside."""
    lo, hi = window.get("trace_window_ns") or (None, None)
    total = 0.0
    for plane in devtrace.device_planes(trace):
        for name, start, dur, *_ in devtrace._line(plane,
                                                   devtrace.MODULE_LINES):
            if readers.kind(name) != readers.QUERY or dur <= 0:
                continue
            a = start if lo is None else max(start, lo)
            b = start + dur if hi is None else min(start + dur, hi)
            total += max(0.0, b - a) / dur
    return total


def read(spans, counters, trace, window):
    shapes = list(window.get("rows_by_shape_in_slice") or {})
    seconds = scope_trace.seconds_in_slice(trace, window,
                                           scope_trace.PAIR_JOIN_SCOPE)
    if not seconds or len(shapes) != 1:
        return None
    programs = programs_in_slice(trace, window)
    if programs <= 0:
        return None
    rows = window["rows_by_shape_in_slice"][shapes[0]]
    mean_rows = sum(rows) / len(rows) if rows else 0.0
    moved = programs * pair_join_model.query_bytes(shapes[0], mean_rows,
                                                   window["store"])
    peak = mesh_trace.peak(window, "peaks.json", "hbm_bytes_per_s")
    return 100.0 * (moved / peak) / seconds
