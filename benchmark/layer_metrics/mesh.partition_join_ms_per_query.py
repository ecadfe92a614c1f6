"""mesh: chip time of the partitioned verified join per query: device
time under the program's scope `mesh.pair_partition` (both sides'
exchange to the key's owner and the local sort-count-expand) in the
traced slice, the mean of the device planes, per query answered in the
slice (`serve.answer` instants, as `ops.device_ms_per_query`).  Nothing
where the trace holds no operation under that scope (a program that
gathers the join's left side instead)."""

from benchmark.harness import mesh_scope, readers


def read(spans, counters, trace, window):
    seconds = mesh_scope.plane_seconds(trace, window,
                                       mesh_scope.PAIR_PARTITION_SCOPE)
    answered = readers.in_slice(spans, window, "serve.answer")
    if not seconds or not sum(seconds) or not answered:
        return None
    return sum(seconds) / len(seconds) * 1e3 / answered
