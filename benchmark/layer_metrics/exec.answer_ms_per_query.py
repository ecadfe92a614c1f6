"""executor: host time from a settled binding table to the answer
string, per answer: spans `exec.materialize` + `exec.format` summed,
over the `serve.answer` instants.  A tree without `exec.format` (older
than PR 26) would read materialisation alone under this name: nothing
is read there."""

from benchmark.harness import readers


def read(spans, counters, trace, window):
    formats = readers.durations_ms(spans, "exec.format")
    answers = sum(1 for s in spans if s["name"] == "serve.answer")
    if not formats or not answers:
        return None
    ms = formats + readers.durations_ms(spans, "exec.materialize")
    return sum(ms) / answers
