"""ops: chip time of the posting-index join's EXPANSION per query:
device time under the program's scope `join.index_expand` (ranges ->
output rows: the slot owner's scatter and running maximum, one packed
row gather of the left side, the reads through `perm` and `targets`;
nested in `join.index_probe`, so `ops.index_join_ms_per_query` holds
it too) in the traced slice, the mean of the device planes, per query
answered in the slice (`serve.answer` instants, as
`ops.device_ms_per_query`).  Nothing where the trace holds no operation
under that scope (a program traced before the scope was there)."""

from benchmark.harness import mesh_scope, readers

#: das_tpu/obs/registry.py INDEX_EXPAND_SCOPE (not imported: the
#: harness reads the program's output, never its modules)
INDEX_EXPAND_SCOPE = "join.index_expand"


def read(spans, counters, trace, window):
    seconds = mesh_scope.plane_seconds(trace, window, INDEX_EXPAND_SCOPE)
    answered = readers.in_slice(spans, window, "serve.answer")
    if not seconds or not sum(seconds) or not answered:
        return None
    return sum(seconds) / len(seconds) * 1e3 / answered
