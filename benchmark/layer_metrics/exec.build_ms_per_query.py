"""executor: host time of building a batch's jobs, per query built: span
`exec.build` (one per dispatched batch that had a job to build, inside
`serve.dispatch`: the per-shape template look-up, the batch's
statistics, the planner's fold and the capacity merge per query)
summed, over the queries those batches built (its `queries` attr: the
cache-missing, de-duplicated ones).  A tree without the span (older
than PR 41) reads nothing."""


def read(spans, counters, trace, window):
    builds = [s for s in spans
              if s["name"] == "exec.build" and s["phase"] == "X"]
    queries = sum(int(s["attrs"].get("queries", 0)) for s in builds)
    if not queries:
        return None
    return sum(s["dur"] for s in builds) * 1e3 / queries
