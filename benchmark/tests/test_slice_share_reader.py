"""`ops.index_join_slice_share` (PR 45): its entry, appended, and its
reader on hand-made counters."""

from benchmark.harness import spec

NAME = "ops.index_join_slice_share"
CELL = "mem-analytic"


def test_the_entry_is_appended_for_the_analytic_cell_alone():
    bench = spec.load_benchmark()
    assert spec.problems(bench) == []
    names = [m["name"] for m in bench["per_layer"]]
    assert names.count(NAME) == 1
    assert bench["per_layer"][names.index(NAME)] == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "ops",
        "moves": "query_p50_ms", "workloads": [CELL]}
    # appended: after every entry the benchmark had (PR 44's five were
    # its last; what later PRs append comes after this one)
    assert names.index(NAME) > names.index("ops.index_join_ms_per_query")
    cell = spec.Cell(CELL)
    assert NAME in {m["name"] for m in cell.per_layer}
    assert "query_p50_ms" in {m["name"] for m in cell.end_to_end}
    for other in bench["workloads"]:
        if other["name"] != CELL:
            listed = {m["name"] for m in spec.Cell(other["name"]).per_layer}
            assert NAME not in listed


def test_the_reader_on_hand_made_counters():
    read = spec.Cell(CELL).layer_reader(NAME)
    # 40 answers, each a first join of 300,000 left rows, all sliced
    both = {"obs.join.index_probe_rows": 12_000_000,
            "obs.join.index_slice_rows": 12_000_000}
    assert read([], both, None, {"answered": 40}) == 100.0
    # a window of small left sides: probed, none sliced
    assert read([], {"obs.join.index_probe_rows": 640,
                     "obs.join.index_slice_rows": 0}, None, {}) == 0.0
    assert read([], {"obs.join.index_probe_rows": 640}, None, {}) == 0.0
    assert read([], {"obs.join.index_probe_rows": 400_000,
                     "obs.join.index_slice_rows": 300_000}, None, {}) == 75.0
    # the parent's side: no such counter; and a window with no such join
    assert read([], {"obs.join.pair_left_rows": 9}, None, {}) is None
    assert read([], {"obs.join.index_probe_rows": 0,
                     "obs.join.index_slice_rows": 0}, None, {}) is None
    assert read([], {}, None, {}) is None
