"""The deployment `flybase-sharded4-analytic`, its cell
`sharded4-analytic` and what PR 47 added to read it: the two entries,
the configuration's file against its two parents (`flybase-sharded4`:
the store and the mesh; `flybase-analytic`: the endpoint), the
interconnect model of the partitioned join by hand, and the five
per-layer readers on hand-made spans, counters and a hand-made profiler
file of TWO device planes."""

import json
import os

import pytest

from benchmark.harness import (ici_join_model, mesh_scope, pair_join_model,
                               spec)
# the hand-made profiler file's writer (fields of the XSpace message)
from test_analytic_cell import _entry, _field

CELL = "sharded4-analytic"
CONFIG = "flybase-sharded4-analytic"
NEW = {
    "mesh.partition_join_ms_per_query": ("ms", "lower", "device_trace"),
    "mesh.partition_join_roofline": ("%", "higher", "device_trace"),
    "mesh.exchange_ici_roofline": ("%", "higher", "device_trace"),
    "mesh.left_rows_gathered_per_query": ("rows/query", "lower",
                                          "program_counter"),
    "mesh.exchange_fill": ("%", "higher", "program_counter"),
}


@pytest.fixture(scope="module")
def cell():
    return spec.Cell(CELL)


def _config(name):
    with open(os.path.join(spec.BENCH_DIR, "configs", name + ".json")) as fh:
        return json.load(fh)


# -- the entries and the files they name -------------------------------------


def test_the_cell_loads_with_everything_it_names(cell):
    assert cell.workload == {
        "name": CELL, "config": CONFIG, "traffic": "analytic-closed",
        "chips": 4, "why": cell.workload["why"]}
    assert len(cell.workload["why"]) <= 200
    assert list(cell.queries) == ["three_var"]
    assert cell.rules["three_var"].KEY is None
    assert [m["name"] for m in cell.end_to_end] == [
        "query_p50_ms", "query_p95_ms", "setup_s"]
    bench = cell.bench
    assert spec.problems(bench) == []
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    # at most half of the cells, rounded down, ask for four chips
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four == 2 <= len(bench["workloads"]) // 2
    entry = bench["configs"][-1]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert entry["reduced"] == ["scale"] == list(cell.config["reduced"])
    assert entry["file"] == f"benchmark/configs/{CONFIG}.json"


def test_the_new_metrics_are_listed_for_the_cell_alone(cell):
    by_name = {m["name"]: m for m in cell.bench["per_layer"]}
    for name, (unit, better, source) in NEW.items():
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": "mesh", "moves": "query_p50_ms", "workloads": [CELL]}
        assert callable(cell.layer_reader(name))
    listed = {m["name"] for m in cell.per_layer}
    assert listed == set(NEW) | {
        "wire.overhead_ms", "coalesce.queue_ms", "exec.compiles_in_window",
        "ops.device_ms_per_query", "device.idle_share",
        "device.peak_mem_bytes"}


def test_the_configuration_against_its_two_parents(cell):
    mine, mesh, endpoint = (cell.config, _config("flybase-sharded4"),
                            _config("flybase-analytic"))
    # the store and the mesh: flybase-sharded4's, at its scale or under
    for key in ("shape", "backend", "chips", "durable", "serve"):
        assert mine[key] == mesh[key], key
    assert mine["backend"] == "sharded" and mine["chips"] == 4
    assert mine["scale"] in (0.3, 0.2, 0.1) and mine["scale"] <= mesh["scale"]
    # the endpoint: flybase-analytic's
    assert mine["das_config"] == endpoint["das_config"] == {
        "result_cache_size": 0, "query_deadline_ms": 120000}
    assert mine["guarantees"] == endpoint["guarantees"] + [
        mesh["guarantees"][-1]]
    assert "shard's rows add up" in mine["guarantees"][-1]
    assert set(mine["assumed"]) >= {
        "shards", "partitioning", "skew", "query_deadline_ms",
        "result_cache_size", "distinct_pairs"}
    assert mine["name"] == CONFIG and "QUERY_1" in mine["source"]
    assert "redis-cluster-start.sh" in mine["source"]


def test_every_das_config_key_is_a_dasconfig_field(cell):
    import dataclasses

    from das_tpu.core.config import DasConfig

    fields = {f.name for f in dataclasses.fields(DasConfig)}
    assert set(cell.config["das_config"]) <= fields


# -- the interconnect model, by hand -----------------------------------------

STORE = {"n_genes": 720_000, "links": 8_361_000, "members_per_gene": 10,
         "mean_out_degree": 1.25}


def test_the_interconnect_model_on_the_cells_store():
    # (S - 1) / S of: 9 M left rows x 12 B + 7.2 M Member rows x 16 B
    assert ici_join_model.LEFT_ROW_BYTES == 12
    assert ici_join_model.query_bytes("three_var", STORE, 4) == 0.75 * (
        108_000_000 + 115_200_000)
    assert ici_join_model.query_bytes("three_var", STORE, 2) == 0.5 * (
        223_200_000)
    assert ici_join_model.query_bytes("three_var", STORE, 1) == 0.0
    with pytest.raises(KeyError):
        ici_join_model.query_bytes("grounded3", STORE, 4)


# -- a hand-made profiler file of two device planes --------------------------


def _plane(device: int, ops, modules) -> bytes:
    """`ops` = [(name, scope path, start_ns, dur_ns)] on the `XLA Ops`
    line, `modules` = [(name, start_ns, dur_ns)]."""
    plane = _field(2, f"/device:TPU:{device}")
    plane += _field(5, _entry(7, _field(1, 7) + _field(2, "tf_op")))
    events, module_events = b"", b""
    for i, (name, scope, start, dur) in enumerate(ops, 1):
        meta = (_field(1, i) + _field(2, name)
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
    return _field(1, plane)


ROOT_SCOPE = "jit(das_sharded)/jit(main)/jit(shmap_body)/"
PART = ROOT_SCOPE + "mesh.pair_partition/"


def _ops(scale: float):
    """One chip's operations of one program; `scale` stretches the
    partitioned join's (the second plane runs it a fifth longer)."""
    ms = 1_000_000
    return [
        # the first join: gathers its left, not under the scope
        ("%all-gather.1 = s32[64]{0} all-gather()",
         ROOT_SCOPE + "mesh.gather_packed/all_gather:", 2_000, 1 * ms),
        ("%fusion.5 = s32[64]{0} fusion()",
         ROOT_SCOPE + "join.index_probe/gather:", 2 * ms, 300 * ms),
        # the partitioned join: a sort that fills the send buffer, the
        # exchange (a copy beside the collective), the local verify
        ("%sort.2 = (s32[64]{0}) sort()", PART + "sort:",
         400 * ms, int(10 * ms * scale)),
        ("%fusion.9 = s32[64]{0} fusion()",
         PART + "mesh.repartition/all_to_all:", 420 * ms, 1 * ms),
        ("%all-to-all.3 = s32[64]{0} all-to-all()",
         PART + "mesh.repartition/all_to_all:", 421 * ms, int(4 * ms * scale)),
        ("%sort.4 = (s32[64]{0}) sort()",
         PART + "join.pair_verify/sort:", 430 * ms, int(20 * ms * scale)),
        # another scope's collective
        ("%all-reduce.6 = s32[] all-reduce()",
         ROOT_SCOPE + "mesh.global_sum/psum:", 480 * ms, 1 * ms),
    ]


MODULES = [("jit_das_sharded(77)", 1_000, 500_000_000),
           ("jit_das_sharded(77)", 600_000_000, 500_000_000)]
PLANES = [_ops(1.0), _ops(1.2)]


@pytest.fixture()
def xplane(tmp_path):
    path = tmp_path / "mesh.xplane.pb"
    path.write_bytes(b"".join(_plane(d, ops, MODULES)
                              for d, ops in enumerate(PLANES))
                     + _field(1, _field(2, "/host:CPU")))
    return str(path)


def _trace(planes=PLANES):
    """`devtrace.load_xplane`'s form of the same planes."""
    return {"planes": [
        {"name": f"/device:TPU:{d}", "lines": [
            {"name": "XLA Modules",
             "events": [[n, float(s), float(t), {}] for n, s, t in MODULES]},
            {"name": "XLA Ops",
             "events": [[n, float(s), float(t), {}] for n, _p, s, t in ops]}]}
        for d, ops in enumerate(planes)]}


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


def test_scope_seconds_chip_by_chip(xplane):
    window = _window(xplane)
    # sort + copy + all-to-all + verify, the second chip a fifth longer
    # (but for the copy)
    assert mesh_scope.plane_seconds(
        _trace(), window, "mesh.pair_partition") == pytest.approx(
        [0.035, 0.0418])
    assert mesh_scope.plane_seconds(
        _trace(), window, "mesh.repartition") == pytest.approx(
        [0.005, 0.0058])
    # the collective alone, not the copy under the same scope
    assert mesh_scope.plane_seconds(
        _trace(), window, "mesh.repartition", collectives=True
    ) == pytest.approx([0.004, 0.0048])
    assert mesh_scope.plane_seconds(
        _trace(), window, "mesh.nothing") == [0.0, 0.0]
    assert mesh_scope.plane_seconds(None, window, "mesh.repartition") is None
    # two programs a chip, whole in the slice
    assert mesh_scope.programs_per_plane(_trace(), window) == 2.0
    # a slice that cuts the second program in half
    cut = _window(xplane, trace_window_ns=[0, 850_000_000])
    assert mesh_scope.programs_per_plane(_trace(), cut) == pytest.approx(1.5)


def test_partition_join_ms_per_query(cell, xplane):
    read = cell.layer_reader("mesh.partition_join_ms_per_query")
    spans = _answers(4) + _answers(3, t=20.0)       # three outside the slice
    assert read(spans, {}, _trace(), _window(xplane)) == pytest.approx(
        (35.0 + 41.8) / 2 / 4)
    assert read(spans, {}, None, _window(xplane)) is None
    assert read([], {}, _trace(), _window(xplane)) is None
    # a program that gathers the left instead: nothing under the scope
    bare = [[op for op in ops if "mesh.pair_partition" not in op[1]]
            for ops in PLANES]
    path = xplane + ".bare"
    with open(path, "wb") as fh:
        fh.write(b"".join(_plane(d, ops, MODULES)
                          for d, ops in enumerate(bare)))
    assert read(spans, {}, _trace(bare), _window(path)) is None


def test_partition_join_roofline(cell, xplane):
    read = cell.layer_reader("mesh.partition_join_roofline")
    per_query = pair_join_model.query_bytes("three_var", 1_650, STORE)
    want = 100.0 * (2.0 * per_query / 2 / 819e9) / ((0.035 + 0.0418) / 2)
    got = read([], {}, _trace(), _window(xplane))
    assert got == pytest.approx(want) and 0 < got < 100
    two = _window(xplane, rows_by_shape_in_slice={"three_var": [1],
                                                  "grounded3": [1]})
    assert read([], {}, _trace(), two) is None
    assert read([], {}, None, _window(xplane)) is None


def test_exchange_ici_roofline(cell, xplane):
    read = cell.layer_reader("mesh.exchange_ici_roofline")
    moved = 2.0 * ici_join_model.query_bytes("three_var", STORE, 2)
    want = 100.0 * (moved / 200e9) / (0.004 + 0.0048)
    got = read([], {}, _trace(), _window(xplane))
    assert got == pytest.approx(want) and 0 < got < 100
    # the HLO name alone, after the program's primitive: still found;
    # and a lowering with no collective's name: the scope's operations
    for rename, seconds in ((lambda n: "%all_to_all.6", 0.004 + 0.0048),
                            (lambda n: "%fusion.77", 0.005 + 0.0058)):
        planes = [[(rename(n) if "all-to-all" in n else n, p, s, t)
                   for n, p, s, t in ops] for ops in PLANES]
        assert read([], {}, _trace(planes), _window(xplane)) == pytest.approx(
            100.0 * (moved / 200e9) / seconds)
    # one plane is no mesh; no exchange under the scope is no reading
    one = xplane + ".one"
    with open(one, "wb") as fh:
        fh.write(_plane(0, PLANES[0], MODULES))
    assert read([], {}, _trace(PLANES[:1]), _window(one)) is None
    assert read([], {}, None, _window(xplane)) is None


def test_left_rows_gathered_per_query(cell):
    read = cell.layer_reader("mesh.left_rows_gathered_per_query")
    window = {"answered": 40}
    assert read([], {"obs.mesh.left_gathered_rows": 40 * 1_048_576}, None,
                window) == 1_048_576
    # a program that partitions every join it could: 0 is a reading
    assert read([], {"obs.mesh.left_gathered_rows": 0}, None, window) == 0
    assert read([], {}, None, window) is None
    assert read([], {"obs.mesh.left_gathered_rows": 5}, None,
                {"answered": 0}) is None


def test_exchange_fill(cell):
    read = cell.layer_reader("mesh.exchange_fill")
    counters = {"obs.mesh.exchange_rows_max": 40 * 562_500,
                "obs.mesh.exchange_slots": 40 * 1_048_576}
    assert read([], counters, None, {}) == pytest.approx(53.644, abs=1e-3)
    assert read([], {"obs.mesh.exchange_slots": 0}, None, {}) is None
    assert read([], {}, None, {}) is None
