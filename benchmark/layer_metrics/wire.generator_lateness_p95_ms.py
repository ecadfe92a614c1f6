"""wire + DSL: how late the open loop's generator ran: 95th percentile
of (really sent - due by the schedule) over every request of the window.
Latency counts from the due time, so a starved generator must not be
read as a slow (or, its queue never filling, a fast) server.  A closed
loop has no schedule: nothing to read."""

from benchmark.harness import stats


def read(spans, counters, trace, window):
    if window.get("loop") != "open" or not window.get("lateness_ms"):
        return None
    return stats.percentile(window["lateness_ms"], 0.95)
