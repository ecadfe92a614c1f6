"""The reduction from a profiler trace to busy time, idle gaps and time
by operation, on a small recorded trace (12 programs of cell 1 on one
v5e, `data/trace_v5e_12_programs.json`) and on synthetic events."""

import json
import os

import pytest

from benchmark.harness import devtrace

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "trace_v5e_12_programs.json")) as fh:
        return json.load(fh)


def test_merged_intervals_unions_and_clips():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 30, 5], ["d", 32, 1], ["e", 50, 0]]
    assert devtrace.merged_intervals(ev) == [[0, 15], [30, 35]]
    assert devtrace.merged_intervals(ev, lo=12, hi=33) == [[12, 15], [30, 33]]


def test_recorded_trace_busy_modules_and_ops(trace):
    assert [p["name"] for p in devtrace.device_planes(trace)] == ["/device:TPU:0"]
    assert devtrace.module_count(trace) == 12
    # 12 programs of 1.3-1.45 ms each
    assert devtrace.module_seconds(trace) == pytest.approx(0.0167, rel=0.02)
    lo, hi = devtrace.trace_extent(trace)
    busy = devtrace.busy_seconds(trace, lo, hi)
    # ops of under 2 us were dropped from the recording, so the union of
    # the ops is a little under the programs' time and never over it
    assert 0.8 * devtrace.module_seconds(trace) < busy <= devtrace.module_seconds(trace)
    assert busy < (hi - lo) / 1e9
    top = devtrace.seconds_by_name(trace, top=3)
    assert len(top) == 3 and top[0][1] >= top[1][1] >= top[2][1] > 0
    assert sum(s for _, s in devtrace.seconds_by_name(trace, top=10 ** 6)) \
        >= busy                          # summed per op >= their union


def test_recorded_trace_gaps_and_attribution(trace):
    lo, hi = devtrace.trace_extent(trace)
    gaps = devtrace.idle_gaps(trace, lo, hi, top=5)
    assert gaps and gaps == sorted(gaps, key=lambda g: -g[1])
    busy = devtrace.busy_seconds(trace, lo, hi)
    all_gaps = devtrace.idle_gaps(trace, lo, hi, top=10 ** 6)
    assert sum(d for _, d in all_gaps) / 1e9 + busy == pytest.approx(
        (hi - lo) / 1e9, rel=1e-9)
    offset = devtrace.sync_offset_ns(trace)
    assert offset == 5_000_000_500.0 - 500.0
    # a worker span that covers the longest gap names it; the rest is unnamed
    g0, d0 = gaps[0]
    spans = [["serve.settle", (g0 + offset) / 1e9 - 1e-4, d0 / 1e9 + 2e-4]]
    named = dict(devtrace.attribute_gaps(all_gaps, spans, offset))
    assert named["serve.settle"] >= d0 / 1e9
    assert devtrace.NO_SPAN in named
    assert sum(named.values()) == pytest.approx(
        sum(d for _, d in all_gaps) / 1e9)
    unaligned = dict(devtrace.attribute_gaps(all_gaps, spans, None))
    assert list(unaligned) == ["host: clocks not aligned"]


def test_a_gap_is_named_by_the_innermost_span_of_the_worker_thread():
    """The worker's spans nest (drain > dispatch > plan); a gap takes the
    name of the span whose OWN time covers most of it, never of the
    outer one that merely contains it."""
    worker = [
        ["serve.drain", 10.000, 0.100],
        ["serve.dispatch", 10.010, 0.050],
        ["serve.plan", 10.020, 0.010],
        ["exec.dispatch", 10.035, 0.020],
        ["serve.settle", 10.070, 0.025],
        ["serve.drain", 10.200, 0.010],
    ]
    segs = devtrace.innermost_segments(worker)
    assert [(round(a, 3), round(b, 3), n) for a, b, n in segs] == [
        (10.0, 10.01, "serve.drain"), (10.01, 10.02, "serve.dispatch"),
        (10.02, 10.03, "serve.plan"), (10.03, 10.035, "serve.dispatch"),
        (10.035, 10.055, "exec.dispatch"), (10.055, 10.06, "serve.dispatch"),
        (10.06, 10.07, "serve.drain"), (10.07, 10.095, "serve.settle"),
        (10.095, 10.1, "serve.drain"), (10.2, 10.21, "serve.drain")]
    offset = 0.0                       # trace ns == perf_counter ns

    def gap(a, b):
        return [a * 1e9, (b - a) * 1e9]

    gaps = [gap(10.021, 10.029),       # inside serve.plan
            gap(10.036, 10.054),       # inside exec.dispatch
            gap(10.056, 10.072),       # dispatch 4, drain 10, settle 2 ms
            gap(10.110, 10.190),       # between two drains: nothing open
            gap(10.195, 10.204)]       # 5 ms of nothing, 4 ms of a drain
    named = dict(devtrace.attribute_gaps(gaps, worker, offset))
    assert named == {
        "serve.plan": pytest.approx(0.008),
        "exec.dispatch": pytest.approx(0.018),
        "serve.drain": pytest.approx(0.016),
        devtrace.NO_SPAN: pytest.approx(0.089)}
    # the parent's rule (the span that covers most of the gap) would
    # have read serve.drain for the first three: the outer span wins
    assert "serve.dispatch" not in named
    # no worker thread found (a tree without serve.drain): all unnamed
    assert dict(devtrace.attribute_gaps(gaps, [], offset)) == {
        devtrace.NO_SPAN: pytest.approx(0.131)}
    assert devtrace.attribute_gaps([], worker, offset) == []


def test_a_trace_without_device_ops_reads_nothing():
    host_only = {"planes": [{"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [["x", 0.0, 5.0, {}]]}]}]}
    assert devtrace.device_planes(host_only) == []
    assert devtrace.busy_seconds(host_only) == 0.0
    assert devtrace.idle_gaps(host_only, 0, 10) == []
    assert devtrace.sync_offset_ns(host_only) is None


def test_layer_readers_return_nothing_without_a_trace():
    from benchmark.harness.spec import Cell, load_benchmark

    cell = Cell(load_benchmark()["workloads"][0]["name"])
    window = {"latency_ms": [], "histograms": {}, "commits": 0,
              "memory": {"peak_bytes_in_use": 0}, "slice_t0": 0, "slice_t1": 1}
    for m in cell.per_layer:
        if m["name"] == "exec.compiles_in_window":
            continue                    # a count: 0 is a reading
        assert cell.layer_reader(m["name"])([], {}, None, window) is None, m
