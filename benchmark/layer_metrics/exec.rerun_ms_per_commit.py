"""executor: host time of sending queries again after a commit overtook
their dispatched round, per commit of the window: span `serve.rerun`
(`route="round"`: planning, building and enqueueing the stale rest of a
group as one new round; `route="per_query"`: one blocking query of the
per-query dispatcher) summed, over the commits.  The re-run round's
fetch, verdicts and answers are under their own names.  Beside it:
`exec.reruns_per_commit` counts the queries.  A tree without the span
(older than PR 42), and a window without commits, read nothing."""

from benchmark.harness import readers


def read(spans, counters, trace, window):
    reruns = readers.durations_ms(spans, "serve.rerun")
    if not reruns or not window["commits"]:
        return None
    return sum(reruns) / window["commits"]
