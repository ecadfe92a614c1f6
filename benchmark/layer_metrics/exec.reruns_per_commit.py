"""executor: queries re-run one by one because a commit overtook their
dispatched round (the program's `exec.stale_reruns` counter), per commit
of the window."""


def read(spans, counters, trace, window):
    if not window["commits"] or "obs.exec.stale_reruns" not in counters:
        return None
    return counters["obs.exec.stale_reruns"] / window["commits"]
