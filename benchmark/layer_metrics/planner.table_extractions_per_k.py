"""planner: whole-table supports BUILT in the window (the program's
`planner.table_extractions` counter, PR 35: each is one `planner.stats`
span of what="table_sparse", ~150 ms of the worker at scale 0.3), per
1,000 answers.  On a read-only store it stands still after warm-up (0);
a commit moves it once per joined table."""


def read(spans, counters, trace, window):
    built = counters.get("obs.planner.table_extractions")
    if built is None or not window.get("answered"):
        return None
    return 1e3 * built / window["answered"]
