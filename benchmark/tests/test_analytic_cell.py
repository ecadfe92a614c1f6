"""The deployment `flybase-analytic`, its cell `mem-analytic` and what PR
44 added to read it: the files the cell names, the plain reference rule,
the bytes model on hand-worked numbers, and the five per-layer readers
on hand-made spans, counters and a hand-made profiler file."""

import dataclasses
import json
import os

import pytest

from benchmark.harness import pair_join_model, scope_trace, spec
from benchmark.harness import traffic as traffic_mod

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "mem-analytic"
NEW = {
    "ops.pair_join_ms_per_query": ("ms", "lower", "device_trace", "ops",
                                   "query_p50_ms"),
    "ops.pair_join_roofline": ("%", "higher", "device_trace", "ops",
                               "query_p50_ms"),
    "ops.pair_join_left_rows_per_query": ("rows/query", "lower",
                                          "program_counter", "ops",
                                          "query_p50_ms"),
    "exec.capacity_retries_per_query": ("retries/query", "lower",
                                        "program_counter", "executor",
                                        "query_p95_ms"),
    "ops.index_join_ms_per_query": ("ms", "lower", "device_trace", "ops",
                                    "query_p50_ms"),
}


@pytest.fixture(scope="module")
def cell():
    return spec.Cell(CELL)


# -- the files the cell names ------------------------------------------------


def test_the_cell_loads_with_everything_it_names(cell):
    assert cell.workload == {
        "name": CELL, "config": "flybase-analytic",
        "traffic": "analytic-closed", "chips": 1, "why": cell.workload["why"]}
    assert len(cell.workload["why"]) <= 200
    assert list(cell.queries) == ["three_var"]
    assert "{key}" not in cell.queries["three_var"]["dsl"]
    assert cell.rules["three_var"].KEY is None
    assert [m["name"] for m in cell.end_to_end] == [
        "query_p50_ms", "query_p95_ms", "setup_s"]
    bench = cell.bench
    assert spec.problems(bench) == []
    assert len(bench["workloads"]) == 5
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    entry = next(c for c in bench["configs"] if c["name"] == "flybase-analytic")
    assert len(entry["source"]) <= 200 and entry["reduced"] == ["scale", "chips"]
    assert sorted(cell.config["reduced"]) == sorted(entry["reduced"])


def test_the_store_is_flybase_mems_letter_for_letter(cell):
    with open(os.path.join(spec.BENCH_DIR, "configs", "flybase-mem.json")) as fh:
        mem = json.load(fh)
    assert cell.config["shape"] == mem["shape"]
    assert cell.config["backend"] == mem["backend"] == "tensor"
    assert cell.config["serve"] == mem["serve"]
    assert cell.config["chips"] == 1 and cell.config["durable"] is False
    assert 0.1 <= cell.config["scale"] <= 0.3


def test_every_das_config_key_is_a_dasconfig_field(cell):
    from das_tpu.core.config import DasConfig

    fields = {f.name for f in dataclasses.fields(DasConfig)}
    assert set(cell.config["das_config"]) == {"result_cache_size",
                                              "query_deadline_ms"}
    assert set(cell.config["das_config"]) <= fields
    assert cell.config["das_config"]["result_cache_size"] == 0
    # the deadline is one number with two sides: the wait of a program
    # that cannot serve the query (2 D + 30 s) and the first program's
    # compile (under D / 2)
    assert 30_000 <= cell.config["das_config"]["query_deadline_ms"] <= 120_000
    assert any("deadline" in g for g in cell.config["guarantees"])


def test_the_mix_validates_and_is_one_keyless_shape(cell):
    traffic_mod.validate(cell.traffic)
    assert cell.traffic["loop"] == "closed" and cell.traffic["clients"] == 4
    assert cell.traffic["queries"] == [{"shape": "three_var", "per_block": 1}]
    assert cell.traffic["writes"] is None
    assert cell.traffic["warmup"]["requests_per_client"] == 1
    plan = traffic_mod.ClientPlan(cell.traffic, 1000, 5, 0, 4)
    assert {plan.next()[0] for _ in range(8)} == {"three_var"}


def test_the_rule_is_the_test_only_one_and_imports_nothing(cell):
    """`test_rules.py` pins `reference/rules/` at two files and may not
    be edited here; its check of a rule file is held here for the third."""
    rules_dir = os.path.join(spec.BENCH_DIR, "reference", "rules")
    names = sorted(f for f in os.listdir(rules_dir) if f.endswith(".py"))
    assert set(names) >= {"grounded3.py", "shared2.py", "three_var.py"}
    with open(os.path.join(rules_dir, "three_var.py")) as fh:
        text = fh.read()
    assert "import" not in text
    mine = spec.load_rule("three_var")
    theirs = spec.load_rule("three_var", os.path.join(HERE, "data"))
    assert mine.COLUMNS == theirs.COLUMNS and mine.KEY is theirs.KEY is None
    assert (mine.rows.__code__.co_code == theirs.rows.__code__.co_code
            and mine.rows.__code__.co_consts == theirs.rows.__code__.co_consts)


def test_the_rule_answers_as_the_test_only_one():
    from benchmark.reference import generator, plain

    kb = plain.PlainKB(generator.Store(0.002, 2**31 + 44))
    mine = spec.load_rule("three_var").rows(kb, None)
    theirs = spec.load_rule("three_var", os.path.join(HERE, "data")).rows(kb)
    assert mine == theirs and 1000 < len(mine) < 2500


# -- the benchmark's entries -------------------------------------------------


def test_the_new_metrics_are_listed_for_the_cell_alone(cell):
    by_name = {m["name"]: m for m in cell.bench["per_layer"]}
    for name, (unit, better, source, layer, moves) in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves, "workloads": [CELL]}
        assert callable(cell.layer_reader(name))
    assert [m["name"] for m in cell.bench["per_layer"][-5:]] == list(NEW)
    # what the cell reports besides: the list-less metrics
    listed = {m["name"] for m in cell.per_layer}
    assert listed == set(NEW) | {
        "wire.overhead_ms", "coalesce.queue_ms", "exec.compiles_in_window",
        "ops.device_ms_per_query", "device.idle_share",
        "device.peak_mem_bytes"}
    # with the result cache off the program counts no look-up, so the
    # hit share has nothing to read here: it keeps to the cells it had
    assert by_name["exec.cache_hit_share"]["workloads"] == [
        w["name"] for w in cell.bench["workloads"][:4]]


# -- the bytes model, on hand-worked numbers ---------------------------------

STORE = {"n_genes": 720_000, "links": 8_361_000, "members_per_gene": 10,
         "mean_out_degree": 1.25}


def test_the_bytes_model_on_the_cells_store():
    # 720,000 genes x 1.25 interaction rows x 10 memberships
    assert pair_join_model.left_rows(STORE) == 9_000_000
    assert pair_join_model.right_rows(STORE) == 7_200_000
    # 9 M x 3 x 4 + 7.2 M x 16 + 1,667 x 3 x 4
    assert pair_join_model.query_bytes("three_var", 1_667, STORE) == (
        108_000_000 + 115_200_000 + 20_004)
    with pytest.raises(KeyError):
        pair_join_model.query_bytes("grounded3", 1, STORE)


# -- a hand-made profiler file -----------------------------------------------


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        byte, n = n & 0x7F, n >> 7
        out.append(byte | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key: int, message: bytes) -> bytes:
    return _field(1, key) + _field(2, message)


def _xspace(ops, modules) -> bytes:
    """One device plane: `ops` = [(name, scope path, start_ns, dur_ns)]
    on the `XLA Ops` line, `modules` = [(name, start_ns, dur_ns)]."""
    plane = _field(2, "/device:TPU:0")
    plane += _field(5, _entry(7, _field(1, 7) + _field(2, "tf_op")))
    plane += _field(5, _entry(8, _field(1, 8) + _field(2, "flops")))
    events, module_events = b"", b""
    for i, (name, scope, start, dur) in enumerate(ops, 1):
        meta = (_field(1, i) + _field(2, name)
                + _field(5, _field(1, 8) + _field(3, 12))
                + _field(5, _field(1, 7) + _field(5, scope)))
        plane += _field(4, _entry(i, meta))
        events += _field(4, _field(1, i) + _field(2, (start - 1000) * 1000)
                         + _field(3, dur * 1000))
    for j, (name, start, dur) in enumerate(modules, 100):
        plane += _field(4, _entry(j, _field(1, j) + _field(2, name)))
        module_events += _field(4, _field(1, j)
                                + _field(2, (start - 1000) * 1000)
                                + _field(3, dur * 1000))
    plane += _field(3, _field(2, "XLA Ops") + _field(3, 1000) + events)
    plane += _field(3, _field(2, "XLA Modules") + _field(3, 1000)
                    + module_events)
    host = _field(2, "/host:CPU")
    return _field(1, plane) + _field(1, host)


SCOPED = "jit(das_fused)/join/join.pair_verify/"
OPS = [
    # the first join: not under the scope
    ("%fusion.5 = s32[64]{0} fusion()", "jit(das_fused)/join/gather:",
     2_000, 500_000_000),
    # the verified join: a sort, and a loop with an operation of its body
    # inside it (counted once)
    ("%sort.9 = (s32[64]{0}) sort()", SCOPED + "sort:",
     600_000_000, 100_000_000),
    ("%while.3 = (s32[]) while()", SCOPED + "jit(searchsorted)/while:",
     800_000_000, 40_000_000),
    ("%fusion.7 = s32[] fusion()", SCOPED + "jit(searchsorted)/while/body/gather:",
     810_000_000, 10_000_000),
    # a name that only looks like the scope
    ("%fusion.8 = s32[] fusion()", "jit(das_fused)/join.pair_verify_not/add:",
     900_000_000, 5_000_000),
]
MODULES = [("jit_das_fused(123)", 1_000, 1_000_000_000),
           ("jit_das_fused(123)", 1_200_000_000, 1_000_000_000)]


@pytest.fixture()
def xplane(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(OPS, MODULES))
    return str(path)


def _trace():
    """`devtrace.load_xplane`'s form of the same plane."""
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules",
         "events": [[n, float(s), float(d), {}] for n, s, d in MODULES]},
        {"name": "XLA Ops",
         "events": [[n, float(s), float(d), {}] for n, _p, s, d in OPS]}]}]}


def test_the_scope_reader_on_a_hand_made_file(xplane):
    ops = scope_trace.device_op_scopes(xplane)
    assert [(p, s, d) for p, s, d in ops] == [
        (p, float(s), float(d)) for _n, p, s, d in OPS]
    assert scope_trace.in_scope(SCOPED + "sort:", "join.pair_verify")
    assert not scope_trace.in_scope("jit(f)/join.pair_verify_not/add:",
                                    "join.pair_verify")
    assert scope_trace.scope_seconds(xplane, "join.pair_verify") == (
        pytest.approx(0.140))
    assert scope_trace.scope_seconds(xplane, "join") == pytest.approx(0.640)
    # clipped to a window that cuts the sort in half
    assert scope_trace.scope_seconds(
        xplane, "join.pair_verify", 650_000_000, 805_000_000) == (
        pytest.approx(0.055))
    assert scope_trace.scope_seconds(xplane, "mesh.gather") == 0.0
    assert scope_trace.own_trace(_trace(), {"xplane_path": xplane}) == xplane


def test_the_runs_own_file_is_found_by_what_it_holds(tmp_path, monkeypatch):
    """`window` does not name the profiler's file, so `own_trace` looks
    under the harness's temporary directories and takes the file that
    holds the loaded trace's operations, never the newest: a directory
    a killed run left, or a run beside this one, is passed over."""
    import tempfile

    monkeypatch.setattr(tempfile, "tempdir", str(tmp_path))

    def leave(run, ops, age):
        d = tmp_path / f"das_bench_{run}" / "device_trace" / "plugins" / (
            "profile") / "2026_10_02"
        d.mkdir(parents=True)
        path = d / "host.xplane.pb"
        path.write_bytes(_xspace(ops, MODULES))
        os.utime(path, (age, age))
        return str(path)

    shifted = [(n, p, s + 7, d) for n, p, s, d in OPS]
    leave("killed", shifted, age=2_000_000_000)         # the newest
    assert scope_trace.own_trace(_trace(), {}) is None
    assert scope_trace.seconds_in_slice(
        _trace(), {"trace_window_ns": None}, "join.pair_verify") is None
    mine = leave("mine", OPS, age=1_000_000_000)
    leave("fewer", OPS[:3], age=2_100_000_000)
    assert scope_trace.own_trace(_trace(), {}) == mine
    assert scope_trace.seconds_in_slice(
        _trace(), {"trace_window_ns": None}, "join.pair_verify") == (
        pytest.approx(0.140))


def _window(xplane, **more):
    window = {"xplane_path": xplane, "slice_t0": 10.0, "slice_t1": 13.0,
              "trace_window_ns": None, "answered": 40,
              "rows_by_shape_in_slice": {"three_var": [1_600, 1_700]},
              "store": STORE, "device_kind": "TPU v5 lite",
              "bench_dir": spec.BENCH_DIR}
    window.update(more)
    return window


def _answers(n, t=11.0):
    return [{"name": "serve.answer", "phase": "i", "t": t + 0.01 * i,
             "dur": 0.0, "thread": "worker", "attrs": {}} for i in range(n)]


def test_pair_join_ms_per_query(cell, xplane):
    read = cell.layer_reader("ops.pair_join_ms_per_query")
    spans = _answers(8) + _answers(3, t=20.0)       # three outside the slice
    assert read(spans, {}, _trace(), _window(xplane)) == pytest.approx(
        140.0 / 8)
    # a program with no such join, an untraced tree, no answer
    bare = str(xplane) + ".bare"
    with open(bare, "wb") as fh:
        fh.write(_xspace(OPS[:1], MODULES))
    assert read(spans, {}, _trace(), _window(bare)) is None
    assert read(spans, {}, None, _window(xplane)) is None
    assert read([], {}, _trace(), _window(xplane)) is None


def test_index_join_ms_per_query(cell, xplane):
    read = cell.layer_reader("ops.index_join_ms_per_query")
    probed = [(n, p.replace("/join/gather:", "/join/join.index_probe/gather:"),
               s, d) for n, p, s, d in OPS]
    scoped = str(xplane) + ".probed"
    with open(scoped, "wb") as fh:
        fh.write(_xspace(probed, MODULES))
    assert read(_answers(8), {}, _trace(), _window(scoped)) == pytest.approx(
        500.0 / 8)
    # the parent's side: the first join sits under no scope of its own
    assert read(_answers(8), {}, _trace(), _window(xplane)) is None
    assert read(_answers(8), {}, None, _window(scoped)) is None


def test_pair_join_roofline(cell, xplane):
    read = cell.layer_reader("ops.pair_join_roofline")
    per_program = pair_join_model.query_bytes("three_var", 1_650, STORE)
    # two whole programs, 0.140 s under the scope, 819 GB/s
    want = 100.0 * (2 * per_program / 819e9) / 0.140
    assert read([], {}, _trace(), _window(xplane)) == pytest.approx(want)
    assert 0 < want < 100
    # a slice that cuts the second program in half counts half of it
    cut = _window(xplane, trace_window_ns=[1_000, 1_700_000_000])
    want_cut = 100.0 * (1.5 * per_program / 819e9) / 0.140
    assert read([], {}, _trace(), cut) == pytest.approx(want_cut)
    # no request wholly inside the slice: the kept rows' term is left out
    none_in = _window(xplane, rows_by_shape_in_slice={"three_var": []})
    assert read([], {}, _trace(), none_in) == pytest.approx(
        100.0 * (2 * pair_join_model.query_bytes("three_var", 0, STORE)
                 / 819e9) / 0.140)
    assert read([], {}, None, _window(xplane)) is None
    two = _window(xplane, rows_by_shape_in_slice={"grounded3": [1],
                                                  "shared2": [2]})
    assert read([], {}, _trace(), two) is None


def test_left_rows_per_query_and_capacity_retries(cell):
    left = cell.layer_reader("ops.pair_join_left_rows_per_query")
    counters = {"obs.join.pair_left_rows": 90_000_000,
                "obs.join.pair_rows": 16_670, "obs.exec.group_lanes": 10}
    assert left([], counters, None, {"answered": 40}) == 9_000_000
    # the parent's side: no such counter
    assert left([], {"obs.exec.group_lanes": 10}, None, {}) is None
    assert left([], {"obs.join.pair_left_rows": 0}, None, {}) is None
    retries = cell.layer_reader("exec.capacity_retries_per_query")
    assert retries([], {"planner.retries": 0}, None, {"answered": 40}) == 0
    assert retries([], {"planner.retries": 4}, None, {"answered": 40}) == 0.1
    assert retries([], {}, None, {"answered": 40}) is None
    assert retries([], {"planner.retries": 0}, None, {"answered": 0}) is None
