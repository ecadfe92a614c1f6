"""mesh: host time the mesh adds to an answer: the pull of the per-shard
result slabs (span `mesh.fetch`, one per settle round) and the assembly
of the shards' rows into one distinct set (span `mesh.dedup`), summed,
over the `serve.answer` instants."""

from benchmark.harness import readers


def read(spans, counters, trace, window):
    fetches = readers.durations_ms(spans, "mesh.fetch")
    answers = sum(1 for s in spans if s["name"] == "serve.answer")
    if not fetches or not answers:
        return None
    return (sum(fetches) + sum(readers.durations_ms(spans, "mesh.dedup"))) \
        / answers
