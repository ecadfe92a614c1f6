"""ops: share of the slice's program time spent in programs that carry
no declared `das_` name (eager jnp ops, anything built outside the
registered builders): what the named metrics cannot see."""

from benchmark.harness import readers


def read(spans, counters, trace, window):
    by_kind = readers.programs_in_slice(trace, window)
    if not by_kind:
        return None
    total = sum(seconds for seconds, _n in by_kind.values())
    # a tree whose programs are all `jit_fn` has no named program to
    # tell the share against: nothing to read
    named = by_kind[readers.QUERY][1] + by_kind[readers.COMMIT][1]
    if total <= 0 or not named:
        return None
    return 100.0 * by_kind[readers.UNNAMED][0] / total
