"""The six per-layer readers of PR 42 and the helper they share
(`harness/worker.py`), on hand-made spans: the reading where the span
or attr is there, None on a tree that lacks it (the parent's side of
the driver's pair), own time less the children's, the per-commit
divisor."""

import pytest

from benchmark.harness import devtrace, worker
from benchmark.harness.spec import Cell, load_benchmark, metrics_of

ALL = ["mem-uniform-closed", "wal-mixed95-closed",
       "sharded4-uniform-closed", "mem-zipf-open"]
NEW = {
    "exec.verdict_ms_per_query": ("executor", "query_p50_ms", ALL),
    "coalesce.resolve_ms_per_query": ("coalescer", "query_p50_ms", ALL),
    "coalesce.settle_unnamed_ms_per_query": ("coalescer", "query_p50_ms", ALL),
    "exec.fetch_wait_share": ("executor", "query_p50_ms", ALL),
    "exec.programs_in_flight": ("executor", "query_p50_ms", ALL),
    "exec.rerun_ms_per_commit": ("executor", "query_p95_ms",
                                 ["wal-mixed95-closed"]),
}


def _span(name, t, dur, thread="worker", **attrs):
    return {"name": name, "phase": "X", "t": t, "dur": dur,
            "thread": thread, "attrs": attrs}


def _answer(t, thread="worker"):
    return {"name": "serve.answer", "phase": "i", "t": t, "dur": 0.0,
            "thread": thread, "attrs": {"error": False}}


def group(t, new=True):
    """One group of two answers on the worker, 100 ms long: a drain, a
    dispatch with two programs, and a settle of 60 ms holding a fetch
    (10 ms, 6 of them waiting), two verdicts (2 ms each), one
    materialize (8 ms) and two formats (1 ms each).  `new`: with the
    spans and attrs of PR 42; without, the tree before it."""
    settle_attrs = {"queries": 2, "lock_wait_ms": 1.0}
    fetch_attrs = {"jobs": 2, "programs": 2}
    if new:
        settle_attrs.update(resolve_ms=4.0)
        fetch_attrs.update(wait_ms=6.0, inflight=3)
    spans = [
        _span("serve.drain", t, 0.005, queries=2),
        _span("serve.dispatch", t + 0.005, 0.030, queries=2),
        _span("exec.dispatch", t + 0.010, 0.004, lanes=2,
              **({"inflight": 1} if new else {})),
        _span("exec.dispatch", t + 0.020, 0.004,
              **({"inflight": 2} if new else {})),
        _span("serve.settle", t + 0.040, 0.060, **settle_attrs),
        _span("exec.settle_fetch", t + 0.041, 0.010, **fetch_attrs),
        _span("exec.materialize", t + 0.060, 0.008, rows=3),
        _span("exec.format", t + 0.070, 0.001, rows=3),
        _span("exec.format", t + 0.080, 0.001, rows=0),
        _answer(t + 0.072), _answer(t + 0.082),
        # a gRPC thread has a span open all the time: it names nothing
        _span("wire.query", t, 0.100, thread="grpc-1"),
    ]
    if new:
        spans += [
            _span("exec.verdict", t + 0.052, 0.002, lanes=2, done=True),
            _span("exec.verdict", t + 0.075, 0.002, lanes=2, done=True),
        ]
    return spans


WINDOW = {"commits": 0}


def read(name, spans, window=WINDOW, cell="wal-mixed95-closed"):
    return Cell(cell).layer_reader(name)(spans, {}, None, window)


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_tree_without_the_span_or_attr_reads_nothing(name):
    old = group(0.0, new=False) + group(1.0, new=False)
    assert read(name, old, {"commits": 4}) is None
    assert read(name, [], {"commits": 4}) is None


def test_verdict_and_resolve_per_answer():
    spans = group(0.0) + group(1.0)
    # 4 verdicts of 2 ms over 4 answers; 2 settles of 4 ms over 4
    assert read("exec.verdict_ms_per_query", spans) == pytest.approx(2.0)
    assert read("coalesce.resolve_ms_per_query", spans) == pytest.approx(2.0)
    # no answer delivered: nothing to divide by
    quiet = [s for s in spans if s["name"] != "serve.answer"]
    assert read("exec.verdict_ms_per_query", quiet) is None
    assert read("coalesce.resolve_ms_per_query", quiet) is None
    # an instant of the name is no duration
    instant = dict(_span("exec.verdict", 0.0, 0.0), phase="i")
    assert read("exec.verdict_ms_per_query", [instant, _answer(0.1)]) is None


def test_settle_unnamed_is_own_time_less_the_clocks():
    spans = group(0.0) + group(1.0)
    # a settle of 60 ms holds 10 + 2 + 8 + 1 + 2 + 1 = 24 ms of children:
    # 36 ms its own, less 1 ms of lock and 4 ms of deliveries = 31 ms a
    # group, two answers a group
    assert worker.own_ms(spans, "serve.settle") == pytest.approx(72.0)
    assert read("coalesce.settle_unnamed_ms_per_query", spans) \
        == pytest.approx(15.5)
    # a child's child is the child's: a dedup inside the materialize
    # takes nothing more off the settle
    nested = spans + [_span("mesh.dedup", 0.062, 0.004)]
    assert read("coalesce.settle_unnamed_ms_per_query", nested) \
        == pytest.approx(15.5)
    assert worker.own_ms(nested, "exec.materialize") == pytest.approx(12.0)
    # a settle on a thread that records no serve.drain is not the worker's
    stray = spans + [_span("serve.settle", 5.0, 1.0, thread="other",
                           resolve_ms=0.0)]
    assert worker.own_ms(stray, "serve.settle") == pytest.approx(72.0)
    assert worker.own_ms(spans, "serve.rerun") is None


def test_fetch_wait_share_and_programs_in_flight():
    spans = group(0.0) + group(1.0)
    assert read("exec.fetch_wait_share", spans) == pytest.approx(60.0)
    assert read("exec.programs_in_flight", spans) == pytest.approx(1.5)
    # a fetch of the tree before (no wait_ms) is left out of both sums
    mixed = group(0.0) + group(1.0, new=False)
    assert read("exec.fetch_wait_share", mixed) == pytest.approx(60.0)
    assert read("exec.programs_in_flight", mixed) == pytest.approx(1.5)
    # 0 is a reading: the outputs were ready, an empty queue
    ready = [_span("exec.settle_fetch", 0.0, 0.002, wait_ms=0.0),
             _span("exec.dispatch", 0.01, 0.001, inflight=0)]
    assert read("exec.fetch_wait_share", ready) == 0.0
    assert read("exec.programs_in_flight", ready) == 0.0


def test_rerun_ms_per_commit_divides_by_the_commits():
    spans = group(0.0) + [
        _span("serve.rerun", 0.090, 0.006, queries=5, route="round"),
        _span("serve.rerun", 1.000, 0.003, queries=1, route="per_query"),
    ]
    assert read("exec.rerun_ms_per_commit", spans, {"commits": 3}) \
        == pytest.approx(3.0)
    assert read("exec.rerun_ms_per_commit", spans, {"commits": 0}) is None
    assert read("exec.rerun_ms_per_commit", group(0.0), {"commits": 3}) is None


def test_worker_segments_are_devtraces_over_the_worker_thread():
    spans = group(10.0)
    mine = [[s["name"], s["t"], s["dur"]] for s in spans
            if s["phase"] == "X" and s["thread"] == "worker"]
    assert worker.own_segments(spans) == devtrace.innermost_segments(mine)
    segments = worker.own_segments(spans)
    assert {n for _a, _b, n in segments} == {
        "serve.drain", "serve.dispatch", "exec.dispatch", "serve.settle",
        "exec.settle_fetch", "exec.verdict", "exec.materialize",
        "exec.format"}
    # disjoint, sorted, and together the thread's time in any span
    assert all(a[1] <= b[0] + 1e-12 for a, b in zip(segments, segments[1:]))
    assert sum(b - a for a, b, _n in segments) == pytest.approx(0.095)
    assert worker.answers(spans) == 2
    assert len(worker.worker_spans(spans)) == len(mine)
    assert worker.attr_values(spans, "exec.dispatch", "inflight") == [1, 2]
    # a flag is no number
    assert worker.attr_values(spans, "exec.verdict", "done") == []


@pytest.mark.parametrize("name", sorted(NEW))
def test_it_is_listed_where_its_end_to_end_metric_is_reported(name):
    bench = load_benchmark()
    (metric,) = [m for m in bench["per_layer"] if m["name"] == name]
    layer, moves, cells = NEW[name]
    assert (metric["layer"], metric["moves"], metric["workloads"]) \
        == (layer, moves, cells)
    assert metric["source"] == "program_span"
    for cell in cells:
        reported = {m["name"] for m in metrics_of(bench, "end_to_end", cell)}
        assert moves in reported
        assert callable(Cell(cell).layer_reader(name))


def test_the_metric_before_them_is_still_listed():
    """PR 41's `exec.build_ms_per_query` was the last entry and is no
    longer (`test_build_reader.py` pins `per_layer[-1]`: conftest.py);
    what else that test holds of the entry, by membership."""
    bench = load_benchmark()
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("exec.build_ms_per_query")
    # this PR's six follow it; what later PRs append comes after them
    assert names[at + 1:at + 1 + len(NEW)] == list(NEW)
    assert names.count(names[at]) == 1
    metric = bench["per_layer"][at]
    assert metric["workloads"] == [c for c in ALL if not c.startswith("sharded")]
    assert (metric["layer"], metric["source"]) == ("executor", "program_span")
    for cell in metric["workloads"]:
        reported = {m["name"] for m in metrics_of(bench, "end_to_end", cell)}
        assert metric["moves"] in reported
