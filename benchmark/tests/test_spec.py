"""BENCHMARK.json against the contract's naming rules, and the harness
finding every file a cell names."""

import copy
import json
import os

import pytest

from benchmark.harness import spec


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


def test_the_committed_file_has_no_problems(bench):
    assert spec.problems(bench) == []
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert len(json.dumps(bench)) < 64 * 1024


def test_every_cell_finds_its_files_and_readers(bench):
    for w in bench["workloads"]:
        cell = spec.Cell(w["name"])
        assert cell.config["name"] == w["config"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s", "query_p50_ms"}
        for m in cell.per_layer:
            assert callable(cell.layer_reader(m["name"]))
        for shape, q in cell.queries.items():
            rule = cell.rules[shape]
            assert rule.COLUMNS and callable(rule.rows)
            # a keyed rule's dsl carries the key; a whole-store rule's not
            assert ("{key}" in q["dsl"]) == (rule.KEY is not None)


def test_files_are_named_from_name_characters(bench):
    for path in bench["paths"]:
        for base, _dirs, files in os.walk(os.path.join(spec.ROOT, path)):
            if "__pycache__" in base or ".pytest_cache" in base:
                continue
            for f in files:
                assert spec.NAME.match(f), os.path.join(base, f)


@pytest.mark.parametrize("mutate,needle", [
    (lambda b: b["workloads"][0].__setitem__("name", "has space"), "not a name"),
    (lambda b: b["workloads"][0].__setitem__("name", "a/b"), "not a name"),
    (lambda b: b["workloads"][0].__setitem__("name", "x" * 65), "not a name"),
    (lambda b: b["end_to_end"][0].__setitem__("unit", "tokens per second"), "unit"),
    (lambda b: b["end_to_end"][0].__setitem__("unit", "u" * 17), "unit"),
    (lambda b: b["end_to_end"][0].__setitem__("unit", "µs"), "unit"),
    (lambda b: b["end_to_end"][0].__setitem__("better", "faster"), "better"),
    (lambda b: b["end_to_end"][0].__setitem__("source", "program_span"), "source"),
    (lambda b: b["end_to_end"][0].__setitem__("bound", 0.5), "bound"),
    (lambda b: b["per_layer"][0].__setitem__("moves", "nothing"), "moves"),
    (lambda b: b["per_layer"][0].__setitem__("workloads", ["nowhere"]), "no workload"),
    (lambda b: b["workloads"][0].__setitem__("config", "ghost"), "no config"),
    (lambda b: b["workloads"][0].__setitem__("chips", 2), "chips"),
    (lambda b: b["workloads"][0].__setitem__("why", "y" * 201), "why"),
])
def test_what_the_contract_refuses_is_found(bench, mutate, needle):
    b = copy.deepcopy(bench)
    mutate(b)
    assert any(needle in p for p in spec.problems(b)), spec.problems(b)


def test_an_unknown_name_fails_loudly():
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.Cell("no-such-cell")
    cell = spec.Cell(spec.load_benchmark()["workloads"][0]["name"])
    with pytest.raises(spec.SpecError, match="no reader"):
        cell.layer_reader("no.such_metric")
