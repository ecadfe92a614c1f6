"""wire + DSL layer: what the RPC adds around the coalescer.

Median client-side latency of a query minus the median time between the
program's `serve.submit` and `serve.answer` instants of one trace id
(both inside the server, DAS_TPU_TRACE spans): gRPC, protobuf, DSL
parse, thread hand-off and the answer's way back."""

from benchmark.harness import stats


def read(spans, counters, trace, window):
    born = {}
    inside = []
    for s in spans:
        if s["name"] == "serve.submit":
            born[s["trace"]] = s["t"]
        elif s["name"] == "serve.answer" and s["trace"] in born:
            inside.append((s["t"] - born.pop(s["trace"])) * 1e3)
    if not inside or not window["latency_ms"]:
        return None
    return (stats.percentile(window["latency_ms"], 0.5)
            - stats.percentile(inside, 0.5))
