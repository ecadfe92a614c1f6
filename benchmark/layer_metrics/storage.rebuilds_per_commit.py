"""storage: full index rebuilds per commit of the window (the program's
`commit.rebuilds` counter; an incremental commit counts under
`commit.deltas` instead)."""


def read(spans, counters, trace, window):
    if not window["commits"]:
        return None
    return counters.get("obs.commit.rebuilds", 0) / window["commits"]
