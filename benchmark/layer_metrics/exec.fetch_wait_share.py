"""executor: share of a settle round's host transfer that the worker
spent waiting for the device and not copying: attr `wait_ms` of span
`exec.settle_fetch` (with tracing on the fetch first waits for the
round's outputs, then copies them) summed, over the summed duration of
those spans.  High: the worker reached the fetch before the device had
finished (the device, or a queue of programs ahead of it, sets the
pace of that round); low: the outputs were ready and the time is the
copy.  A tree without the attr (older than PR 42) reads nothing."""


def read(spans, counters, trace, window):
    wait = wall = 0.0
    for s in spans:
        if s["name"] == "exec.settle_fetch" and s["phase"] == "X" \
                and "wait_ms" in s["attrs"]:
            wait += s["attrs"]["wait_ms"]
            wall += s["dur"] * 1e3
    if wall <= 0:
        return None
    return 100.0 * wait / wall
