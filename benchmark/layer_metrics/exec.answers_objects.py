"""executor: answers of the window whose assignment OBJECTS were built
(the program's `exec.answers_objects` counter, PR 32) instead of their
HANDLE text being printed from the block of distinct rows
(`exec.answers_block`).  A count: on the served HANDLE path it is 0, and
0 is a reading; one object per row cost 10.3 ms a 1.3 k-row answer."""


def read(spans, counters, trace, window):
    if "obs.exec.answers_block" not in counters:
        return None
    return counters.get("obs.exec.answers_objects", 0)
