"""The per-layer readers PR 26 added, each on synthetic spans, counters
and a synthetic modules line: the reading where there is something to
read, None where there is not (an untraced run, a tree that lacks the
span or the program name, a window with no commits)."""

import pytest

from benchmark.harness import readers
from benchmark.harness.spec import Cell, load_benchmark

CELL2 = "wal-mixed95-closed"


@pytest.fixture(scope="module")
def read():
    cell = Cell(CELL2)
    names = {m["name"] for m in cell.per_layer}
    return lambda name, *a: (cell.layer_reader(name)(*a)
                             if name in names else pytest.fail(name))


def span(name, t, ms, phase="X", **attrs):
    return {"name": name, "phase": phase, "t": t, "dur": ms / 1e3,
            "trace": 0, "group": 0, "attrs": attrs}


def window(**kw):
    out = {"latency_ms": [], "histograms": {}, "commits": 0,
           "memory": {"peak_bytes_in_use": 0}, "slice_t0": 10.0,
           "slice_t1": 13.0, "trace_window_ns": [1000.0, 3_000_001_000.0]}
    out.update(kw)
    return out


def trace(*modules):
    """modules: (name, start_ns, dur_ns) on the modules line of one
    device plane (with one op, so that the plane counts as a device)."""
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules",
         "events": [[n, float(s), float(d), {}] for n, s, d in modules]},
        {"name": "XLA Ops", "events": [["%fusion", 1000.0, 10.0, {}]]}]}]}


NAMED = trace(
    ("jit_das_fused(1)", 2_000, 1_000_000),
    ("jit_das_fused_count(2)", 2_000_000, 3_000_000),
    ("jit_das_merge_padded(3)", 10_000_000, 900_000_000),
    ("jit_das_insert_rows(4)", 950_000_000, 100_000_000),
    ("jit_concatenate(5)", 1_100_000_000, 4_000_000),
    # straddles the end of the slice: clipped to 1 ms inside it
    ("jit_das_fused(6)", 2_999_001_000, 5_000_000),
    # wholly outside the slice
    ("jit_das_merge_padded(7)", 3_500_000_000, 900_000_000),
)
OLD_TREE = trace(("jit_fn(1)", 2_000, 1_400_000),
                 ("jit_fn(2)", 5_000_000, 900_000_000))


def test_program_names_and_kinds():
    assert readers.program_name("jit_das_fused(6095367381246586959)") == \
        "das_fused"
    assert readers.kind("jit_das_count_batch(1)") == readers.QUERY
    assert readers.kind("jit_das_merge_sharded(1)") == readers.COMMIT
    assert readers.kind("jit_das_insert_rows(1)") == readers.COMMIT
    assert readers.kind("jit_fn(1)") == readers.UNNAMED
    by_kind = readers.programs_in_slice(NAMED, window())
    assert by_kind[readers.QUERY] == [pytest.approx(0.005), 3]
    assert by_kind[readers.COMMIT] == [pytest.approx(1.0), 2]
    assert by_kind[readers.UNNAMED] == [pytest.approx(0.004), 1]
    assert readers.programs_in_slice(None, window()) is None


def test_ops_query_program_ms(read):
    w = window()
    assert read("ops.query_program_ms", [], {}, NAMED, w) == \
        pytest.approx(5.0 / 3)
    assert read("ops.query_program_ms", [], {}, OLD_TREE, w) is None
    assert read("ops.query_program_ms", [], {}, None, w) is None


def test_storage_merge_device_ms_per_commit(read):
    name = "storage.merge_device_ms_per_commit"
    commits = [span("commit.delta", 10.5, 0, "i"),
               span("commit.delta", 12.0, 0, "i"),
               span("commit.delta", 14.0, 0, "i")]     # after the slice
    assert read(name, commits, {}, NAMED, window()) == pytest.approx(500.0)
    assert read(name, [], {}, NAMED, window()) is None      # no commit
    assert read(name, commits, {}, OLD_TREE, window()) is None
    assert read(name, commits, {}, None, window()) is None


def test_ops_unnamed_device_share(read):
    name = "ops.unnamed_device_share"
    assert read(name, [], {}, NAMED, window()) == pytest.approx(
        100.0 * 0.004 / 1.009)
    # a tree with no declared name at all: nothing to tell it against
    assert read(name, [], {}, OLD_TREE, window()) is None
    assert read(name, [], {}, None, window()) is None


def test_exec_reruns_per_commit(read):
    name = "exec.reruns_per_commit"
    assert read(name, [], {"obs.exec.stale_reruns": 200}, None,
                window(commits=20)) == 10.0
    assert read(name, [], {"obs.exec.stale_reruns": 0}, None,
                window(commits=20)) == 0.0
    assert read(name, [], {}, None, window(commits=20)) is None  # old tree
    assert read(name, [], {"obs.exec.stale_reruns": 3}, None,
                window(commits=0)) is None


@pytest.mark.parametrize("metric,span_name", [
    ("storage.stage_ms", "commit.stage"),
    ("storage.wal_append_ms", "dur.wal_append"),
    ("wire.parse_ms", "wire.parse"),
])
def test_span_medians(read, metric, span_name):
    spans = [span(span_name, 1.0, 4.0), span(span_name, 2.0, 6.0),
             span(span_name, 3.0, 50.0), span("serve.plan", 1.0, 999.0)]
    assert read(metric, spans, {}, None, window()) == pytest.approx(6.0)
    # the same name as an instant (a tree older than PR 26) has no duration
    old = [span(span_name, 1.0, 0.0, "i")]
    assert read(metric, old, {}, None, window()) is None
    assert read(metric, [], {}, None, window()) is None


def test_planner_stats_ms_per_commit(read):
    name = "planner.stats_ms_per_commit"
    spans = [span("planner.stats", 1.0, 30.0, rows=5),
             span("planner.stats", 2.0, 50.0, rows=7)]
    assert read(name, spans, {}, None, window(commits=4)) == 20.0
    assert read(name, spans, {}, None, window(commits=0)) is None
    assert read(name, [], {}, None, window(commits=4)) is None


def test_planner_plan_ms_per_query(read):
    name = "planner.plan_ms_per_query"
    spans = [span("serve.plan", 1.0, 12.0, queries=20, compilable=20),
             span("serve.plan", 2.0, 8.0, queries=20, compilable=19)]
    assert read(name, spans, {}, None, window()) == pytest.approx(0.5)
    assert read(name, [], {}, None, window()) is None


def test_exec_answer_ms_per_query(read):
    name = "exec.answer_ms_per_query"
    spans = [span("exec.materialize", 1.0, 2.0), span("exec.format", 1.1, 3.0),
             span("exec.format", 1.2, 1.0),
             span("serve.answer", 1.3, 0, "i"), span("serve.answer", 1.4, 0, "i"),
             span("serve.answer", 1.5, 0, "i")]
    assert read(name, spans, {}, None, window()) == pytest.approx(2.0)
    assert read(name, spans[:3], {}, None, window()) is None   # no answers
    assert read(name, spans[3:], {}, None, window()) is None   # no spans
    # an older tree: materialisation alone is another quantity
    assert read(name, spans[:1] + spans[3:], {}, None, window()) is None


def test_host_gil_wait_share(read):
    name = "host.gil_wait_share"
    spans = [span("serve.plan", 1.0, 10.0, cpu_ms=4.0),
             span("exec.materialize", 1.1, 6.0, cpu_ms=2.0),
             span("exec.format", 1.2, 4.0, cpu_ms=4.0),
             # blocking spans are no part of it
             span("serve.settle", 1.0, 500.0, cpu_ms=1.0)]
    assert read(name, spans, {}, None, window()) == pytest.approx(50.0)
    # spans of a tree whose recorder takes no CPU clock
    old = [span("serve.plan", 1.0, 10.0), span("exec.materialize", 1.1, 6.0)]
    assert read(name, old, {}, None, window()) is None
    assert read(name, [], {}, None, window()) is None
    # thread_time can run a hair past perf_counter: never below zero
    hot = [span("serve.plan", 1.0, 1.0, cpu_ms=1.01)]
    assert read(name, hot, {}, None, window()) == 0.0


def test_coalesce_lock_wait_ms(read):
    name = "coalesce.lock_wait_ms"
    hist = {"serve.lock_wait_ms": {"p50": 0.001, "p95": 0.5, "count": 400}}
    assert read(name, [], {}, None, window(histograms=hist)) == 0.5
    empty = {"serve.lock_wait_ms": {"p50": None, "p95": None, "count": 0}}
    assert read(name, [], {}, None, window(histograms=empty)) is None
    assert read(name, [], {}, None, window()) is None        # old tree


def test_every_new_metric_is_declared_with_a_reader():
    bench = load_benchmark()
    new = {"ops.query_program_ms", "storage.merge_device_ms_per_commit",
           "ops.unnamed_device_share", "exec.reruns_per_commit",
           "storage.stage_ms", "storage.wal_append_ms",
           "planner.stats_ms_per_commit", "planner.plan_ms_per_query",
           "exec.answer_ms_per_query", "host.gil_wait_share",
           "coalesce.lock_wait_ms", "wire.parse_ms"}
    declared = {m["name"]: m for m in bench["per_layer"]}
    assert new <= set(declared)
    cell = Cell(CELL2)
    for name in new:
        assert CELL2 in declared[name]["workloads"]
        assert callable(cell.layer_reader(name))
