"""executor: host time of judging a settled job, per answer: span
`exec.verdict` (one per job of a settle round, inside `serve.settle`:
the job's lane taken out of the fetched block, its stats read, the
result object built or the capacities grown, the result cache's
insert) summed, over the `serve.answer` instants.  A tree without the
span (older than PR 42) reads nothing."""

from benchmark.harness import readers, worker


def read(spans, counters, trace, window):
    verdicts = readers.durations_ms(spans, "exec.verdict")
    answered = worker.answers(spans)
    if not verdicts or not answered:
        return None
    return sum(verdicts) / answered
