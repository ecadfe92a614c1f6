"""planner: host time of query planning per query: span `serve.plan`
(one per dispatched group) summed, over the queries those groups held
(its `queries` attr)."""


def read(spans, counters, trace, window):
    plans = [s for s in spans if s["name"] == "serve.plan"]
    queries = sum(int(s["attrs"].get("queries", 0)) for s in plans)
    if not queries:
        return None
    return sum(s["dur"] for s in plans) * 1e3 / queries
