"""`exec.lanes_per_program` (PR 30) on synthetic counters: lanes over
programs where the program's two counters moved, None where they are
absent (a tree without them, an untraced run)."""

import pytest

from benchmark.harness.spec import Cell, load_benchmark, metrics_of

NAME = "exec.lanes_per_program"


@pytest.mark.parametrize("cell", ["mem-uniform-closed", "wal-mixed95-closed",
                                  "sharded4-uniform-closed", "mem-zipf-open"])
def test_exec_lanes_per_program(cell):
    read = Cell(cell).layer_reader(NAME)
    w = {"answered": 5230}
    # 220 groups of two shapes: 440 programs carried 5,230 jobs
    got = read([], {"obs.exec.group_programs": 440,
                    "obs.exec.group_lanes": 5230}, None, w)
    assert got == pytest.approx(5230 / 440)
    # every job its own program (the mesh, a tree of PR 29)
    assert read([], {"obs.exec.group_programs": 3442,
                     "obs.exec.group_lanes": 3442}, None, w) == 1.0
    # the parent has no such counters; an untraced run leaves them at 0
    assert read([], {"obs.exec.dispatches": 5230}, None, w) is None
    assert read([], {"obs.exec.group_programs": 0,
                     "obs.exec.group_lanes": 0}, None, w) is None
    assert read([], {}, None, w) is None


def test_it_is_listed_where_its_end_to_end_metric_is_reported():
    bench = load_benchmark()
    (metric,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert metric["layer"] == "executor"
    for cell in metric["workloads"]:
        reported = {m["name"] for m in metrics_of(bench, "end_to_end", cell)}
        assert metric["moves"] in reported
