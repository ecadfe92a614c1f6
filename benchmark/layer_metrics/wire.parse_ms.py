"""wire + DSL: median of span `wire.parse`: query text -> AST on the
gRPC thread, the first part of `wire.overhead_ms`."""

from benchmark.harness import readers


def read(spans, counters, trace, window):
    return readers.median_ms(spans, "wire.parse")
