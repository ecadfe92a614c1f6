"""executor: jobs carried per device program the shared dispatch loop
enqueued over the window (the program's counters `exec.group_lanes` /
`exec.group_programs`: first rounds and capacity retries, one chip and
mesh).  1.0 where every query is its own program; a same-signature group
that rides one program counts its lanes."""


def read(spans, counters, trace, window):
    programs = counters.get("obs.exec.group_programs")
    lanes = counters.get("obs.exec.group_lanes")
    if not programs or lanes is None:
        return None
    return lanes / programs
