"""coalescer: median wait from submit to group dispatch, the program's
`serve.queue_ms` histogram (log buckets: within ~19 % of the sample
quantile) over the window."""


def read(spans, counters, trace, window):
    h = window["histograms"].get("serve.queue_ms")
    if not h or not h["count"]:
        return None
    return h["p50"]
