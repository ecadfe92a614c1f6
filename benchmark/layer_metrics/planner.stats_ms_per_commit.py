"""planner: host time spent recomputing the planner's statistics (span
`planner.stats`: the estimator's rebuild after a commit and every
uncached whole-table extraction), summed over the window, per commit."""

from benchmark.harness import readers


def read(spans, counters, trace, window):
    ms = readers.durations_ms(spans, "planner.stats")
    if not ms or not window["commits"]:
        return None
    return sum(ms) / window["commits"]
