"""device: share of the traced slice in which no operation ran on the
chip: 1 - union of the device-op intervals / slice."""

from benchmark.harness import devtrace


def read(spans, counters, trace, window):
    if trace is None or "trace_window_ns" not in window:
        return None
    lo, hi = window["trace_window_ns"]
    if hi <= lo:
        return None
    busy = devtrace.busy_seconds(trace, lo, hi)
    return 100.0 * (1.0 - busy / ((hi - lo) / 1e9))
