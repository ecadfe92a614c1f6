"""coalescer: wall time of delivering an answer, per answer: attr
`resolve_ms` of span `serve.settle` (summed over the group's
`Future.set_result` calls: the condition's notify, the callbacks, the
`serve.answer` instant; the woken gRPC thread then competes with the
worker for the interpreter) summed, over the `serve.answer` instants.
A tree without the attr (older than PR 42) reads nothing."""

from benchmark.harness import worker


def read(spans, counters, trace, window):
    resolve = worker.attr_values(spans, "serve.settle", "resolve_ms")
    answered = worker.answers(spans)
    if not resolve or not answered:
        return None
    return sum(resolve) / answered
