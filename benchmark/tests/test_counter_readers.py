"""The readers this PR adds, each on synthetic inputs: the reading where
there is something to read, None where there is not."""

import pytest

from benchmark.harness.spec import Cell, load_benchmark, metrics_of

CELLS = [w["name"] for w in load_benchmark()["workloads"]]
NEW = ("planner.table_extractions_per_k", "exec.answers_objects",
       "wire.generator_lateness_p95_ms")


@pytest.mark.parametrize("cell", CELLS)
def test_planner_table_extractions_per_k(cell):
    read = Cell(cell).layer_reader("planner.table_extractions_per_k")
    w = {"answered": 2930}
    # cell 2 (PR 35): one extraction a commit, 143 commits, 2,930 reads
    assert read([], {"obs.planner.table_extractions": 143}, None, w) == \
        pytest.approx(48.805, rel=1e-4)
    # a read-only store after warm-up: the counter stands still
    assert read([], {"obs.planner.table_extractions": 0}, None, w) == 0.0
    # a tree without the counter; a window that answered nothing
    assert read([], {"obs.planner.table_hits": 9}, None, w) is None
    assert read([], {"obs.planner.table_extractions": 1}, None,
                {"answered": 0}) is None


@pytest.mark.parametrize("cell", CELLS)
def test_exec_answers_objects(cell):
    read = Cell(cell).layer_reader("exec.answers_objects")
    # the served HANDLE path prints from the block: 0 objects is a reading
    assert read([], {"obs.exec.answers_block": 15950,
                     "obs.exec.answers_objects": 0}, None, {}) == 0
    assert read([], {"obs.exec.answers_block": 15000,
                     "obs.exec.answers_objects": 950}, None, {}) == 950
    # a tree of before PR 32 has neither counter
    assert read([], {"obs.exec.dispatches": 7}, None, {}) is None


def test_wire_generator_lateness_p95_ms():
    read = Cell("mem-zipf-open").layer_reader("wire.generator_lateness_p95_ms")
    late = [0.2] * 94 + [1.0, 2.0, 3.0, 4.0, 5.0, 80.0]
    got = read([], {}, None, {"loop": "open", "lateness_ms": late})
    assert 1.0 < got < 2.0                         # the 95th of 100
    # a closed loop has no schedule; an open one that sent nothing, nothing
    assert read([], {}, None, {"loop": "closed",
                               "lateness_ms": [0.0] * 100}) is None
    assert read([], {}, None, {"loop": "open", "lateness_ms": []}) is None
    assert read([], {}, None, {}) is None


@pytest.mark.parametrize("name", NEW)
def test_each_is_listed_where_its_end_to_end_metric_is_reported(name):
    bench = load_benchmark()
    (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
    for cell in metric["workloads"]:
        reported = {m["name"] for m in metrics_of(bench, "end_to_end", cell)}
        assert metric["moves"] in reported
    if name.startswith("wire."):
        assert metric["workloads"] == ["mem-zipf-open"]
    else:
        assert sorted(metric["workloads"]) == sorted(CELLS)
