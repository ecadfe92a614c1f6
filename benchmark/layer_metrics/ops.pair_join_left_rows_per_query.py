"""ops: rows offered to a verified join (counter `join.pair_left_rows`,
from the program's own stats output) per query JOB of the window
(counter `exec.group_lanes`: the jobs enqueued, one a program on the
lone `das_fused` path).  Says that the verified join ran and
on how much: the rows of the intermediate the planner's join order
leaves before it, 9,000,000 for Interacts x Member at scale 0.3; falls
if the planner finds a cheaper order.  Nothing where the program has no
such counter."""


def read(spans, counters, trace, window):
    left = counters.get("obs.join.pair_left_rows")
    jobs = counters.get("obs.exec.group_lanes")
    if left is None or not jobs:
        return None
    return left / jobs
