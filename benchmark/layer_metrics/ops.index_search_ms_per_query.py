"""ops: chip time of the posting-index join's range SEARCH per query:
device time under the program's scope `join.index_search` (the slice
search proper: the levels of separators and the descent, ONE gather of
a row of separators a level and a probe; not the two table-long passes
that make `words` and `run_end`; nested in `join.index_probe`, so
`ops.index_join_ms_per_query` holds it too) in the traced slice, the
mean of the device planes, per query answered in the slice
(`serve.answer` instants, as `ops.device_ms_per_query`).  Nothing where
the trace holds no operation under that scope (a program traced before
the scope was there: its search was a `while` loop of 22 one-word
gathers, found in `breakdown.device_ops` by that name)."""

from benchmark.harness import mesh_scope, readers

#: das_tpu/obs/registry.py INDEX_SEARCH_SCOPE (not imported: the
#: harness reads the program's output, never its modules)
INDEX_SEARCH_SCOPE = "join.index_search"


def read(spans, counters, trace, window):
    seconds = mesh_scope.plane_seconds(trace, window, INDEX_SEARCH_SCOPE)
    answered = readers.in_slice(spans, window, "serve.answer")
    if not seconds or not sum(seconds) or not answered:
        return None
    return sum(seconds) / len(seconds) * 1e3 / answered
