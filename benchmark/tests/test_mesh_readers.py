"""The seven `mesh.*` readers and `ici_model` (PR 29) on synthetic spans,
counters and a synthetic trace with four device planes: the reading
where there is something to read, None where there is not (an untraced
run, one chip, a tree that lacks the span or the counter)."""

import os

import pytest

from benchmark.harness import hbm_model, ici_model, mesh_trace
from benchmark.harness.spec import BENCH_DIR, Cell

CELL3 = "sharded4-uniform-closed"
STORE = {"n_genes": 720_000, "links": 8_361_000, "members_per_gene": 10,
         "mean_out_degree": 1.25}
MS = 1_000_000.0  # ns


@pytest.fixture(scope="module")
def read():
    cell = Cell(CELL3)
    names = {m["name"] for m in cell.per_layer}
    return lambda name, *a: (cell.layer_reader(name)(*a)
                             if name in names else pytest.fail(name))


def span(name, t, ms, phase="X", **attrs):
    return {"name": name, "phase": phase, "t": t, "dur": ms / 1e3,
            "trace": 0, "group": 0, "attrs": attrs}


def window(**kw):
    out = {"latency_ms": [], "histograms": {}, "commits": 0, "answered": 0,
           "memory": {"peak_bytes_in_use": 0}, "slice_t0": 10.0,
           "slice_t1": 13.0, "trace_window_ns": [0.0, 3000 * MS],
           "store": STORE, "bench_dir": BENCH_DIR,
           "device_kind": "TPU v5 lite",
           "rows_by_shape_in_slice": {"grounded3": [], "shared2": []}}
    out.update(kw)
    return out


def plane(i, ops, modules=()):
    return {"name": f"/device:TPU:{i}", "lines": [
        {"name": "XLA Modules",
         "events": [[n, float(s), float(d), {}] for n, s, d in modules]},
        {"name": "XLA Ops",
         "events": [[n, float(s), float(d), {}] for n, s, d in ops]}]}


def four_planes(extra_on_first=0.0):
    """Each chip: 6 ms of compute, 1 ms all-gather, 0.5 + 0.5 ms of an
    asynchronous all-reduce's halves, all inside one 10 ms program; the
    first chip may run longer."""
    planes = []
    for i in range(4):
        compute = 6 * MS + (extra_on_first if i == 0 else 0.0)
        planes.append(plane(i, [
            ("%fusion.1", 0, compute),
            ("%all-gather.3", compute, 1 * MS),
            ("%all-reduce-start.1", compute + 1 * MS, 0.5 * MS),
            ("%all-reduce-done.1", compute + 1.5 * MS, 0.5 * MS),
        ], [("jit_das_sharded(7)", 0, 10 * MS),
            ("jit_concatenate(8)", 20 * MS, 1 * MS)]))
    return {"planes": planes + [{"name": "/host:CPU", "lines": []}]}


ONE_PLANE = {"planes": [plane(0, [("%fusion.1", 0, 6 * MS),
                                  ("%all-reduce.2", 6 * MS, 1 * MS)],
                              [("jit_das_sharded(7)", 0, 10 * MS)])]}


def test_collectives_are_told_from_compute():
    yes = ["%all-gather.3", "%all-reduce-start.1", "%all-reduce-done",
           "%all-to-all", "%collective-permute-done.2", "%reduce-scatter.1",
           "%ag = s32[64,3]{1,0} all-gather(s32[16,3]{1,0} %p), dimensions={0}",
           "%ar = (s32[], s32[]) all-reduce-start(s32[] %a, s32[] %b)"]
    no = ["%fusion.1", "%while.38", "%custom-call.4", "%copy-start",
          "%gather.2", "%reduce-window.1", "%all = s32[4]{0} add(%a, %b)"]
    assert all(mesh_trace.is_collective(n) for n in yes)
    assert not any(mesh_trace.is_collective(n) for n in no)


def test_mesh_collective_share(read):
    w = window()
    # 2 ms of collectives in 8 ms busy on every chip
    assert read("mesh.collective_share", [], {}, four_planes(), w) == \
        pytest.approx(25.0)
    assert read("mesh.collective_share", [], {}, None, w) is None
    assert read("mesh.collective_share", [], {}, {"planes": []}, w) is None


def test_mesh_device_skew(read):
    w = window()
    assert read("mesh.device_skew", [], {}, four_planes(), w) == \
        pytest.approx(1.0)
    # the first chip busy 12 ms, the others 8: 12 / 9
    assert read("mesh.device_skew", [], {}, four_planes(4 * MS), w) == \
        pytest.approx(12.0 / 9.0)
    assert read("mesh.device_skew", [], {}, ONE_PLANE, w) is None
    assert read("mesh.device_skew", [], {}, None, w) is None


def test_ici_model_is_the_remote_share_of_the_matched_rows():
    for shape, rows in (("grounded3", 0), ("grounded3", 3), ("shared2", 1333)):
        matched = ici_model.matched_rows(shape, rows, STORE)
        assert ici_model.query_bytes(shape, rows, STORE, 4) == \
            pytest.approx(0.75 * matched * hbm_model.ROW_BYTES)
        assert ici_model.query_bytes(shape, rows, STORE, 1) == 0.0
        # never more than the rows' share of what HBM must move
        assert ici_model.query_bytes(shape, rows, STORE, 4) < \
            hbm_model.query_bytes(shape, rows, STORE)
    assert ici_model.matched_rows("shared2", 1333, STORE) == 1343
    with pytest.raises(KeyError):
        ici_model.query_bytes("star5", 1, STORE, 4)


def test_mesh_ici_roofline(read):
    rows = {"grounded3": [0] * 90, "shared2": [1333] * 10}
    w = window(rows_by_shape_in_slice=rows)
    moved = sum(ici_model.query_bytes(s, n, STORE, 4)
                for s, per in rows.items() for n in per)
    # 4 chips x 2 ms of collectives = 8 chip-ms at 200e9 bytes/s
    want = 100.0 * (moved / 200e9) / 0.008
    got = read("mesh.ici_roofline", [], {}, four_planes(), w)
    assert got == pytest.approx(want) and 0 < got < 1.0
    assert read("mesh.ici_roofline", [], {}, ONE_PLANE, w) is None
    assert read("mesh.ici_roofline", [], {}, None, w) is None


def test_mesh_ici_roofline_cannot_pass_100_percent(read):
    """The least bytes at the peak take the least time: collectives that
    moved them in less would have beaten the interconnect.  Here the
    slice's queries need 19.3 us of the four links at their peak; a
    trace whose collectives took exactly that reads 100 %, and every
    trace of the real chip reads (far) less."""
    rows = {"grounded3": [], "shared2": [1333] * 1000}
    w = window(rows_by_shape_in_slice=rows)
    moved = 1000 * ici_model.query_bytes("shared2", 1333, STORE, 4)
    least_ns = moved / 200e9 / 4 * 1e9          # per chip, ns
    tight = {"planes": [plane(i, [("%all-gather.1", 0, least_ns)])
                        for i in range(4)]}
    assert read("mesh.ici_roofline", [], {}, tight, w) == pytest.approx(100.0)
    slower = {"planes": [plane(i, [("%all-gather.1", 0, 40 * least_ns)])
                         for i in range(4)]}
    assert read("mesh.ici_roofline", [], {}, slower, w) == pytest.approx(2.5)


def test_mesh_sharded_conj_roofline(read):
    rows = {"grounded3": [0] * 90, "shared2": [1333] * 10}
    w = window(rows_by_shape_in_slice=rows)
    moved = sum(hbm_model.query_bytes(s, n, STORE)
                for s, per in rows.items() for n in per)
    # the das_sharded modules alone: 4 chips x 10 ms
    want = 100.0 * (moved / 819e9) / 0.040
    got = read("mesh.sharded_conj_roofline", [], {}, four_planes(), w)
    assert got == pytest.approx(want) and got < 100.0
    unnamed = {"planes": [plane(0, [("%fusion", 0, MS)],
                                [("jit_fn(1)", 0, 10 * MS)])]}
    assert read("mesh.sharded_conj_roofline", [], {}, unnamed, w) is None
    assert read("mesh.sharded_conj_roofline", [], {}, None, w) is None
    with pytest.raises(KeyError):
        read("mesh.sharded_conj_roofline", [], {}, four_planes(),
             window(device_kind="TPU v9"))


def test_mesh_counter_metrics(read):
    w = window(answered=200)
    counters = {"obs.mesh.collective_bytes": 640_000, "obs.mesh.retries": 3}
    assert read("mesh.collective_bytes_per_query", [], counters, None, w) == \
        pytest.approx(3200.0)
    assert read("mesh.retries_per_query", [], counters, None, w) == \
        pytest.approx(0.015)
    assert read("mesh.retries_per_query", [], {"obs.mesh.retries": 0},
                None, w) == 0.0
    # a tree without the counters (the parent commit); no answers
    assert read("mesh.collective_bytes_per_query", [], {}, None, w) is None
    assert read("mesh.retries_per_query", [], {}, None, w) is None
    assert read("mesh.retries_per_query", [], counters, None, window()) is None


def test_mesh_fetch_ms_per_query(read):
    spans = [span("mesh.fetch", 1.0, 6.0), span("mesh.fetch", 2.0, 4.0),
             span("mesh.dedup", 2.1, 2.0), span("exec.settle_fetch", 1.0, 6.0),
             *[span("serve.answer", 3.0, 0.0, phase="i") for _ in range(4)]]
    assert read("mesh.fetch_ms_per_query", spans, {}, None, window()) == \
        pytest.approx(3.0)
    # a tree without the span (the parent commit)
    old = [s for s in spans if not s["name"].startswith("mesh.")]
    assert read("mesh.fetch_ms_per_query", old, {}, None, window()) is None
    assert read("mesh.fetch_ms_per_query", spans[:3], {}, None,
                window()) is None


def test_every_mesh_metric_has_its_reader_and_lists_the_cell():
    cell = Cell(CELL3)
    mesh = [m for m in cell.per_layer if m["layer"] == "mesh"]
    assert len(mesh) == 7
    for m in mesh:
        assert m["workloads"] == [CELL3]
        assert callable(cell.layer_reader(m["name"]))
        assert os.path.isfile(os.path.join(
            BENCH_DIR, "layer_metrics", m["name"] + ".py"))
    reported = {m["name"] for m in cell.end_to_end}
    assert reported == {"query_p50_ms", "query_p95_ms", "setup_s"}
    assert all(m["moves"] in reported for m in cell.per_layer)
