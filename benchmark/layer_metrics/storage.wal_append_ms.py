"""storage: median of span `dur.wal_append`: capture, pack, write, flush
and fsync of one commit's WAL record.  (An older tree records the name
as an instant; an instant has no duration and is not read.)"""

from benchmark.harness import readers


def read(spans, counters, trace, window):
    return readers.median_ms(spans, "dur.wal_append")
