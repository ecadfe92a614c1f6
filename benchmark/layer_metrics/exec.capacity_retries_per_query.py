"""executor: re-dispatches of a query program after one of its
capacities overflowed (the coalescer's always-on `planner.retries`), per
correct answer.  0 in a window whose capacities were met in warm-up;
each one is a second run of the program, and a larger program built
inside the window where the step is new."""


def read(spans, counters, trace, window):
    retries = counters.get("planner.retries")
    if retries is None or not window.get("answered"):
        return None
    return retries / window["answered"]
