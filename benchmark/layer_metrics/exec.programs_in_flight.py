"""executor: device programs enqueued and not yet fetched as one more
is enqueued (itself not counted): attr `inflight` of span
`exec.dispatch`, its mean over the spans of the run.  The depth of the
device's queue as the one worker thread sees it: near 0 the device
waits for the host between programs, and what a width policy for
coalesced groups has to steer by.  A tree without the attr (older than
PR 42) reads nothing."""

from benchmark.harness import worker


def read(spans, counters, trace, window):
    depth = worker.attr_values(spans, "exec.dispatch", "inflight")
    if not depth:
        return None
    return sum(depth) / len(depth)
