"""mesh: how close the partitioned join's exchange comes to the
interconnect roofline.  The least bytes the verified join of one query
sends between chips when both sides are partitioned (`ici_join_model`:
every row crossing once) for every query program of the traced slice, at
one chip's interconnect peak (`ici_peaks.json`), are chip-seconds; so
are the device seconds of the collective operations under the scope
`mesh.repartition`, summed over the planes (every operation under the
scope where none carries a collective's name: the scope holds the
`all_to_all` call alone, so whatever runs there is its lowering).  Says
whether the interconnect is what the join waits for (it is not, at tens
of MB a chip a program).  Nothing where nothing ran under that scope."""

from benchmark.harness import ici_join_model, mesh_scope, mesh_trace


def read(spans, counters, trace, window):
    seconds = mesh_scope.plane_seconds(
        trace, window, mesh_scope.REPARTITION_SCOPE, collectives=True)
    if seconds and not sum(seconds):
        seconds = mesh_scope.plane_seconds(
            trace, window, mesh_scope.REPARTITION_SCOPE)
    shape = mesh_scope.one_shape(window)
    if not seconds or len(seconds) < 2 or not sum(seconds) or shape is None:
        return None
    programs = mesh_scope.programs_per_plane(trace, window)
    if programs <= 0:
        return None
    moved = programs * ici_join_model.query_bytes(
        shape[0], window["store"], len(seconds))
    peak = mesh_trace.peak(window, "ici_peaks.json", "ici_bytes_per_s")
    return 100.0 * (moved / peak) / sum(seconds)
