"""ops: device time under the program's scope `join.pair_verify` (the
join that verifies every shared column before it counts a row) in the
traced slice, per query answered in it (`serve.answer` instants inside
the slice, as `ops.device_ms_per_query`).  Nothing where the trace
holds no operation under that scope (a program without such a join)."""

from benchmark.harness import scope_trace


def read(spans, counters, trace, window):
    seconds = scope_trace.seconds_in_slice(trace, window,
                                           scope_trace.PAIR_JOIN_SCOPE)
    answered = sum(1 for s in spans if s["name"] == "serve.answer"
                   and window["slice_t0"] <= s["t"] <= window["slice_t1"])
    if not seconds or not answered:
        return None
    return seconds * 1e3 / answered
