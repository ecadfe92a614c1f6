"""ops: device time of ONE query program: the modules of the traced
slice that carry a declared query name (`das_fused*`, `das_count*`,
`das_sharded*`; not the commit's `das_merge*` / `das_insert*`), summed,
over their number.  Unlike `ops.device_ms_per_query` it does not mix a
commit's merge programs into the queries' time."""

from benchmark.harness import readers


def read(spans, counters, trace, window):
    by_kind = readers.programs_in_slice(trace, window)
    if not by_kind or not by_kind[readers.QUERY][1]:
        return None
    seconds, n = by_kind[readers.QUERY]
    return seconds * 1e3 / n
