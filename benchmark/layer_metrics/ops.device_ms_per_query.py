"""ops: device time of whole XLA programs in the traced slice, per query
answered in it (`serve.answer` instants inside the slice)."""

from benchmark.harness import devtrace


def read(spans, counters, trace, window):
    if trace is None or not devtrace.device_planes(trace):
        return None
    answered = sum(1 for s in spans if s["name"] == "serve.answer"
                   and window["slice_t0"] <= s["t"] <= window["slice_t1"])
    if not answered:
        return None
    return devtrace.module_seconds(trace) * 1e3 / answered
