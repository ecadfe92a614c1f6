"""mesh: how close the partitioned verified join comes to the HBM
roofline.  The bytes the join must move for one query
(`pair_join_model`: the SAME model as `ops.pair_join_roofline`, so it
reads the same work whatever implements it), a chip's share 1 / S of
them, for every query program of the traced slice on a chip (one cut by
the slice's edge counts by the share of it inside) at the device's peak
bandwidth (`peaks.json`), as a share of the chip's device time under the
scope `mesh.pair_partition` in the slice: means of the device planes.
Can only pass 100 % if the scope leaves part of the join's work out."""

from benchmark.harness import mesh_scope, mesh_trace, pair_join_model


def read(spans, counters, trace, window):
    seconds = mesh_scope.plane_seconds(trace, window,
                                       mesh_scope.PAIR_PARTITION_SCOPE)
    shape = mesh_scope.one_shape(window)
    if not seconds or not sum(seconds) or shape is None:
        return None
    programs = mesh_scope.programs_per_plane(trace, window)
    if programs <= 0:
        return None
    a_chip = pair_join_model.query_bytes(*shape, window["store"]) / len(seconds)
    peak = mesh_trace.peak(window, "peaks.json", "hbm_bytes_per_s")
    return (100.0 * (programs * a_chip / peak)
            / (sum(seconds) / len(seconds)))
