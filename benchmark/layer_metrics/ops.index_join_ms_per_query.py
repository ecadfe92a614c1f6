"""ops: device time under the program's scope `join.index_probe` (the
join into a whole-type term on ONE shared variable: two searches of the
posting index, a prefix sum, the expansion of every candidate) in the
traced slice, per query answered in it (`serve.answer` instants inside
the slice, as `ops.device_ms_per_query`).  In the whole-store
conjunction this is the FIRST join, Interacts x Member, and most of the
program.  Nothing where the trace holds no operation under that scope
(a program traced before the scope was there)."""

from benchmark.harness import scope_trace


def read(spans, counters, trace, window):
    seconds = scope_trace.seconds_in_slice(trace, window,
                                           scope_trace.INDEX_JOIN_SCOPE)
    answered = sum(1 for s in spans if s["name"] == "serve.answer"
                   and window["slice_t0"] <= s["t"] <= window["slice_t1"])
    if not seconds or not answered:
        return None
    return seconds * 1e3 / answered
