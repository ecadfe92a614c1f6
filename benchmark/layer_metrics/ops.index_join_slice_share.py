"""ops: of the left rows offered to a posting-index join of ONE shared
variable in the window (counter `join.index_probe_rows`), the share
whose ranges came from the slice search (counter
`join.index_slice_rows`: one binary search on 32-bit words inside the
probed type's slice, the range's end read; the program takes it where
the left side is large against a big index, by static shape), in
percent.  Says that the whole-store conjunction's first join engaged
it: 100 there, 0 where every left side is small.  Both counters come
from the program's own stats output.  Nothing where no probe row was
counted (a program without the counters, or a window without such a
join)."""


def read(spans, counters, trace, window):
    probed = counters.get("obs.join.index_probe_rows")
    if not probed:
        return None
    return 100.0 * counters.get("obs.join.index_slice_rows", 0) / probed
