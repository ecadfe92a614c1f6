"""coalescer: what the account still cannot name inside the settle
loop, per answer: the OWN time of span `serve.settle` on the worker
thread (no child span open: not the fetch, a verdict, materialising,
printing or a re-run round) less its attrs `lock_wait_ms` and
`resolve_ms` (the waits for the tenant lock and the deliveries, which
are clocks and no spans), over the `serve.answer` instants.  What is
left: generator frames, the per-yield staleness check, the binding
table.  A tree whose `serve.settle` carries no `resolve_ms` (older than
PR 42) reads nothing: there the whole own time is unnamed, and the
worker's account has it."""

from benchmark.harness import worker


def read(spans, counters, trace, window):
    resolve = worker.attr_values(spans, "serve.settle", "resolve_ms")
    own = worker.own_ms(spans, "serve.settle")
    answered = worker.answers(spans)
    if not resolve or own is None or not answered:
        return None
    named = sum(resolve) + sum(
        worker.attr_values(spans, "serve.settle", "lock_wait_ms"))
    return (own - named) / answered
