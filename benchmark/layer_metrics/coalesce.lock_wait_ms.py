"""coalescer: 95th percentile of the worker's wait for the tenant lock,
one sample per acquire (dispatch, each settle step, each fall-back
query): the program's `serve.lock_wait_ms` histogram (log buckets:
within ~19 % of the sample quantile) over the window."""


def read(spans, counters, trace, window):
    h = window["histograms"].get("serve.lock_wait_ms")
    if not h or not h["count"]:
        return None
    return h["p95"]
