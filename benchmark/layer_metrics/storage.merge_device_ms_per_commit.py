"""storage: device time a commit's index merge costs: the `das_merge*`
and `das_insert*` programs of the traced slice, summed, over the commits
applied in it (`commit.delta` instants).  The programs of a commit run
after its instant, so a slice cuts the first and the last commit's: with
two or three commits in a slice this reads within about a third of the
true cost."""

from benchmark.harness import readers


def read(spans, counters, trace, window):
    by_kind = readers.programs_in_slice(trace, window)
    if not by_kind or not by_kind[readers.COMMIT][1]:
        return None
    commits = readers.in_slice(spans, window, "commit.delta")
    if not commits:
        return None
    return by_kind[readers.COMMIT][0] * 1e3 / commits
