"""`exec.build_ms_per_query` (PR 41) on synthetic inputs: the reading
where the span is there, None where it is not."""

import pytest

from benchmark.harness.spec import Cell, load_benchmark, metrics_of

NAME = "exec.build_ms_per_query"
ONE_CHIP = ["mem-uniform-closed", "wal-mixed95-closed", "mem-zipf-open"]


def _span(name, dur, **attrs):
    return {"name": name, "phase": "X", "t": 0.0, "dur": dur, "attrs": attrs}


@pytest.mark.parametrize("cell", ONE_CHIP)
def test_exec_build_ms_per_query(cell):
    read = Cell(cell).layer_reader(NAME)
    spans = [
        _span("serve.dispatch", 0.010, queries=21),
        _span("exec.build", 0.004, queries=19, shapes=2, templates_built=0),
        _span("exec.dispatch", 0.001, lanes=17),
        _span("exec.build", 0.002, queries=1, shapes=1, templates_built=1),
    ]
    # 6 ms of building over the 20 queries built
    assert read(spans, {}, None, {}) == pytest.approx(0.3)
    # the parent: job building has no span of its own
    assert read([s for s in spans if s["name"] != "exec.build"],
                {}, None, {}) is None
    assert read([], {}, None, {}) is None
    # an instant of the same name is no duration; a span that built
    # nothing divides nothing
    instant = dict(_span("exec.build", 0.0, queries=5), phase="i")
    assert read([instant], {}, None, {}) is None
    assert read([_span("exec.build", 0.001, queries=0)], {}, None, {}) is None


def test_it_is_listed_where_its_end_to_end_metric_is_reported():
    bench = load_benchmark()
    (metric,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert bench["per_layer"][-1] is metric         # appended, last
    assert metric["workloads"] == ONE_CHIP
    assert (metric["layer"], metric["source"]) == ("executor", "program_span")
    for cell in metric["workloads"]:
        reported = {m["name"] for m in metrics_of(bench, "end_to_end", cell)}
        assert metric["moves"] in reported
