"""storage: median of span `commit.stage`: intern, columnize and the
host cost of ENQUEUEING the device merges of one commit (the merges run
after it: `storage.merge_device_ms_per_commit`)."""

from benchmark.harness import readers


def read(spans, counters, trace, window):
    return readers.median_ms(spans, "commit.stage")
