"""device: `memory_stats()["peak_bytes_in_use"]` after the window."""


def read(spans, counters, trace, window):
    peak = window["memory"]["peak_bytes_in_use"]
    return peak or None
