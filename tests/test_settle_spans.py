"""The settle loop has names (ISSUE 42).

With tracing on, what the coalescer's worker does between a group's
fetch and its last delivered answer is recorded where it happens:
`exec.verdict` per settled job, `resolve_ms` on `serve.settle`, `wait_ms` / `inflight` on `exec.settle_fetch`,
`inflight` on `exec.dispatch`, `serve.rerun` around a stale round's
second dispatch.  Pinned here on the benchmark generator's store at a
small scale, through a real `QueryCoalescer`: the names and attrs, that
the worker thread's spans NEST (nothing is open across a `yield`), that
answers and their order do not depend on tracing, and that with tracing
off the ring stays empty.  `obs.worker_account` (the account PERF.md §5
is made with) is checked on a hand-made nest.
"""

import os
import sys
import time
from concurrent.futures import Future

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from das_tpu import obs  # noqa: E402
from das_tpu.api.atomspace import (  # noqa: E402
    DistributedAtomSpace,
    QueryOutputFormat,
)
from das_tpu.core.config import DasConfig  # noqa: E402
from das_tpu.query import fused  # noqa: E402
from das_tpu.query.ast import And, Link, Node, Variable  # noqa: E402
from das_tpu.service.coalesce import QueryCoalescer  # noqa: E402
from das_tpu.service.server import _Tenant  # noqa: E402

pytestmark = pytest.mark.obs

SCALE, SEED = 0.002, 11
HANDLE = QueryOutputFormat.HANDLE


@pytest.fixture(scope="module")
def das(tmp_path_factory):
    from benchmark.reference import generator

    os.environ["DAS_TPU_XLA_CACHE"] = "0"
    path = os.path.join(str(tmp_path_factory.mktemp("settle")), "kb.metta")
    generator.write_canonical(generator.Store(SCALE, SEED), path)
    das = DistributedAtomSpace(
        database_name="settle", backend="tensor",
        config=DasConfig(result_cache_size=0))
    das.load_canonical_knowledge_base(path)
    return das


@pytest.fixture
def traced():
    obs.configure(enabled=True, capacity=1 << 16)
    obs.reset()
    yield
    obs.reset()
    obs.configure(enabled=False)


def _query(i: int):
    """The cell's mix: `grounded3` nine times in ten, else `shared2`."""
    from benchmark.reference import generator

    g = Node("Gene", generator.gene_name(i))
    clauses = [Link("Member", [g, Variable("V3")], True),
               Link("Member", [Variable("V2"), Variable("V3")], True)]
    if i % 10 != 9:
        clauses.append(Link("Interacts", [g, Variable("V2")], True))
    return And(clauses)


def _serve(das, queries, coal=None):
    """Answers of `queries` through a real coalescer worker, in the
    order they were DELIVERED too; returns once the worker is idle
    (every group's `serve.settle` closed)."""
    coal = coal or QueryCoalescer(max_batch=16, pipeline_depth=2)
    tenant = _Tenant("settle", das)
    order = []
    futs = []
    for n, q in enumerate(queries):
        f = coal.submit(tenant, q, HANDLE)
        f.add_done_callback(lambda _f, n=n: order.append(n))
        futs.append(f)
    answers = [f.result(timeout=300) for f in futs]
    deadline = time.time() + 10
    while time.time() < deadline:
        names = [e[0] for e in obs.events()]
        if names.count("serve.settle") == names.count("serve.dispatch"):
            break
        time.sleep(0.01)
    return answers, order


def _worker_spans(events):
    workers = {e[7] for e in events if e[0] == "serve.drain"}
    assert len(workers) == 1, workers
    return [e for e in events if e[1] == "X" and e[7] in workers]


def _inside(inner, outer, eps=1e-7):
    return (outer[2] - eps <= inner[2]
            and inner[2] + inner[3] <= outer[2] + outer[3] + eps)


def _children(events, parent, name):
    return [e for e in events if e[0] == name and e[7] == parent[7]
            and e is not parent and _inside(e, parent)]


# -- tracing on: the names, the attrs, the nesting ------------------------


@pytest.fixture
def served(das, traced):
    queries = [_query(i) for i in range(40)]
    das.query_many(queries[:20], HANDLE)     # programs built, caps learned
    obs.reset()
    answers, _order = _serve(das, queries)
    return queries, answers, obs.events()


def test_every_settled_job_has_one_verdict_inside_a_settle(served):
    queries, _answers, events = served
    verdicts = [e for e in events if e[0] == "exec.verdict"]
    settles = [e for e in events if e[0] == "serve.settle"]
    done = [v for v in verdicts if v[8]["done"]]
    # distinct genes, no result cache: a job a query, one final verdict
    # a job (a verdict with done=False is a capacity retry's)
    assert len(done) == len(queries)
    for v in verdicts:
        assert v[8]["lanes"] >= 1 and v[8]["done"] in (True, False)
        assert sum(1 for s in settles if s[7] == v[7] and _inside(v, s)) == 1
    fetched = sum(e[8]["jobs"] for e in events if e[0] == "exec.settle_fetch")
    assert len(verdicts) == fetched


def test_worker_spans_nest(served):
    """No two spans of the worker overlap without one holding the
    other: a span open across a `yield` would break exactly this (the
    consumer's exec.materialize would start inside an exec.verdict and
    end after it)."""
    _queries, _answers, events = served
    spans = sorted(_worker_spans(events), key=lambda e: (e[2], -e[3]))
    assert {"serve.settle", "exec.verdict", "exec.materialize",
            "exec.format", "exec.settle_fetch"} <= {e[0] for e in spans}
    stack = []
    for e in spans:
        start, end = e[2], e[2] + e[3]
        while stack and stack[-1][1] <= start + 1e-9:
            stack.pop()
        if stack:
            assert end <= stack[-1][1] + 1e-7, (
                f"{e[0]} overlaps the end of {stack[-1][0]}")
        stack.append((e[0], end))


def test_settle_carries_the_delivery_clock(served):
    queries, _answers, events = served
    settles = [e for e in events if e[0] == "serve.settle"]
    assert sum(s[8]["streamed"] for s in settles) == len(queries)
    for s in settles:
        attrs = s[8]
        assert attrs["resolve_ms"] > 0.0 and "resolve_cpu_ms" not in attrs
        assert attrs["resolve_ms"] + attrs["lock_wait_ms"] <= s[3] * 1e3
    # a delivery records no event of its own beyond serve.answer
    per_answer = [e[0] for e in events if e[1] == "i"]
    assert set(per_answer) == {"serve.submit", "serve.answer"}


def test_fetch_carries_the_wait_and_the_queue(served):
    _queries, _answers, events = served
    fetches = [e for e in events if e[0] == "exec.settle_fetch"]
    dispatches = [e for e in events if e[0] == "exec.dispatch"]
    assert fetches and dispatches
    for f in fetches:
        assert 0.0 <= f[8]["wait_ms"] <= f[3] * 1e3
        assert 0.0 <= f[8]["cpu_ms"]
        # its own programs are in flight as the fetch begins
        assert f[8]["inflight"] >= f[8]["programs"] >= 1
    assert all(d[8]["inflight"] >= 0 for d in dispatches)
    # the worker's first program found an empty queue
    assert min(dispatches, key=lambda d: d[2])[8]["inflight"] == 0
    # every program enqueued was fetched, and the queue's depth never
    # passes what the window holds: pipeline_depth groups of max_batch
    assert (obs.counter("exec.group_programs").value
            == sum(f[8]["programs"] for f in fetches) == len(dispatches))
    assert max(f[8]["inflight"] for f in fetches) <= 2 * 16
    # the last fetch of the run finds only its own programs queued
    last = max(fetches, key=lambda f: f[2])
    assert last[8]["inflight"] == last[8]["programs"]
    assert not [e for e in events if e[0] == "serve.rerun"]


def _commit(das, n: int):
    from benchmark.reference import generator

    das.load_metta_text(
        f'(Interacts "{generator.gene_name(300 + n)}" '
        f'"{generator.gene_name(301 + n)}")')


def test_commit_between_dispatch_and_settle_is_one_rerun_round(das, traced):
    """The stale rest of a group goes again as ONE round: one
    `serve.rerun` (route="round") holding the re-run's serve.plan and
    exec.dispatch; the dropped round is in flight no longer."""
    coal = QueryCoalescer(max_batch=16, pipeline_depth=2)
    tenant = _Tenant("settle", das)
    queries = [_query(i) for i in range(50, 56)]
    want = das.query_many(queries, HANDLE)
    obs.reset()
    group = [(tenant, q, HANDLE, Future()) for q in queries]
    entry = coal._dispatch_group(tenant, HANDLE, group)
    assert fused.programs_in_flight() >= 1
    _commit(das, 0)                       # overtakes the dispatched round
    coal._settle_group(entry)
    assert [item[3].result(timeout=60) for item in group] == want
    events = obs.events()
    reruns = [e for e in events if e[0] == "serve.rerun"]
    assert len(reruns) == 1
    assert reruns[0][8]["route"] == "round"
    assert reruns[0][8]["queries"] == len(queries)
    settle = [e for e in events if e[0] == "serve.settle"]
    assert len(settle) == 1 and _inside(reruns[0], settle[0])
    assert len(_children(events, reruns[0], "serve.plan")) == 1
    assert len(_children(events, reruns[0], "exec.dispatch")) >= 1
    # the re-run's verdicts stream after the span closed, not inside it
    verdicts = [e for e in events if e[0] == "exec.verdict"]
    assert len(verdicts) == len(queries)
    assert not _children(events, reruns[0], "exec.verdict")
    assert obs.counter("exec.stale_reruns").value == len(queries)
    # the re-run's first enqueue found the queue empty: the round the
    # commit overtook went with its object
    first = min(_children(events, reruns[0], "exec.dispatch"),
                key=lambda e: e[2])
    assert first[8]["inflight"] == 0
    assert fused.programs_in_flight() == 0


def test_a_lone_stale_query_reruns_per_query(das, traced):
    coal = QueryCoalescer(max_batch=16, pipeline_depth=2)
    tenant = _Tenant("settle", das)
    q = _query(60)
    want = das.query(q, HANDLE)
    obs.reset()
    group = [(tenant, q, HANDLE, Future())]
    entry = coal._dispatch_group(tenant, HANDLE, group)
    _commit(das, 2)
    coal._settle_group(entry)
    assert group[0][3].result(timeout=60) == want
    reruns = [e for e in obs.events() if e[0] == "serve.rerun"]
    assert [(r[8]["route"], r[8]["queries"]) for r in reruns] == [
        ("per_query", 1)]


def test_a_round_given_up_before_its_fetch_is_in_flight_no_longer(traced):
    """A commit can land while a group's cache hits are still
    streaming, before its round was fetched: the consumer drops the
    stream there, and the round's programs must not stay in flight for
    good (cell 2 once read `inflight` 24 with ~2 programs really
    queued).  No site has to say so: what is in flight is what the
    live `_PendingMany` objects of the thread hold."""
    from das_tpu.models.bio import build_bio_atomspace
    from das_tpu.query import compiler
    from das_tpu.storage.tensor_db import TensorDB

    data, genes, _procs = build_bio_atomspace(
        n_genes=60, n_processes=15, members_per_gene=4, n_interactions=80,
        seed=7)
    db = TensorDB(data, DasConfig())          # the default result cache
    ex = fused.get_executor(db)

    def plans(g):
        name = db.get_node_name(g)
        return compiler.plan_query(db, And([
            Link("Member", [Node("Gene", name), Variable("V3")], True),
            Link("Member", [Variable("V2"), Variable("V3")], True)]))

    ex.execute_many([plans(genes[0])])        # now a cache hit
    assert fused.programs_in_flight() == 0
    pending = ex.dispatch_many([plans(genes[0]), plans(genes[1])])
    assert fused.programs_in_flight() == len(pending.programs) == 1
    stream = ex.settle_many_iter(pending)
    assert next(stream)[0] == 0               # the hit; nothing fetched yet
    assert fused.programs_in_flight() == 1
    del stream, pending                       # what a stale break does
    assert fused.programs_in_flight() == 0
    # a stream run to its end fetched what it enqueued, object alive
    pending = ex.dispatch_many([plans(genes[2])])
    assert [i for i, _ in ex.settle_many_iter(pending)] == [0]
    assert fused.programs_in_flight() == 0 and pending.programs == []
    # another thread's rounds are not this thread's queue
    import threading

    seen = []
    pending = ex.dispatch_many([plans(genes[3])])
    th = threading.Thread(
        target=lambda: seen.append(fused.programs_in_flight()))
    th.start()
    th.join()
    assert seen == [0] and fused.programs_in_flight() == 1


def test_a_rerun_does_not_count_the_round_it_replaces(traced, tmp_path):
    """The stale break at a cache-hit yield, through the job that
    serves it (api/atomspace.py settle_iter): the round given up there
    was never fetched, and the re-run's enqueue must find it gone."""
    from benchmark.reference import generator
    from das_tpu.api.atomspace import _QueryManyJob

    path = os.path.join(str(tmp_path), "kb.metta")
    generator.write_canonical(generator.Store(SCALE, SEED), path)
    cached = DistributedAtomSpace(database_name="settle_cached",
                                  backend="tensor")
    cached.load_canonical_knowledge_base(path)
    queries = [_query(9), _query(19), _query(29)]      # shared2: rows
    cached.query_many(queries, HANDLE)        # programs built
    _commit(cached, 10)                       # the cache is empty again
    cached.query_many(queries[:2], HANDLE)    # two hits and one miss
    obs.reset()
    job = _QueryManyJob(cached, queries, HANDLE)
    assert fused.programs_in_flight() == 1
    stream = job.settle_iter()
    got = dict([next(stream)])                # the first hit
    assert list(got) == [0] and fused.programs_in_flight() == 1
    _commit(cached, 12)                       # lands between two yields
    got.update(stream)
    # (the commits touch no row these queries read)
    assert [got[i] for i in range(3)] == cached.query_many(queries, HANDLE)
    events = obs.events()
    (rerun,) = [e for e in events if e[0] == "serve.rerun"]
    assert rerun[8] == {"queries": 2, "route": "round",
                        "cpu_ms": rerun[8]["cpu_ms"]}
    again = _children(events, rerun, "exec.dispatch")
    assert again and min(again, key=lambda e: e[2])[8]["inflight"] == 0
    assert fused.programs_in_flight() == 0


# -- tracing must not change what is served --------------------------------


def test_answers_and_their_order_do_not_depend_on_tracing(das):
    queries = [_query(i) for i in range(100, 130)]
    want = das.query_many(queries, HANDLE)
    runs = {}
    for on in (False, True, False):
        obs.configure(enabled=on, capacity=1 << 16)
        obs.reset()
        try:
            # one group after another (depth 1), so delivery order is the
            # settle loop's own and not the race of two groups
            coal = QueryCoalescer(max_batch=64, pipeline_depth=1,
                                  pipeline_depth_max=1)
            tenant = _Tenant("settle", das)
            order = []
            group = []
            for n, q in enumerate(queries):
                f = Future()
                f.add_done_callback(lambda _f, n=n: order.append(n))
                group.append((tenant, q, HANDLE, f))
            coal._settle_group(coal._dispatch_group(tenant, HANDLE, group))
            answers = [item[3].result(timeout=60) for item in group]
        finally:
            recorded = len(obs.events())
            obs.reset()
            obs.configure(enabled=False)
        assert answers == want
        assert (recorded > 0) == on
        runs.setdefault(on, []).append(order)
    assert runs[True][0] == runs[False][0] == runs[False][1]
    assert sorted(runs[True][0]) == list(range(len(queries)))


def test_tracing_off_records_nothing_and_keeps_no_tally(das):
    """The disabled contract (tests/test_zobs.py pins its structure):
    no span object, an empty ring, untouched counters, no round kept
    for the in-flight reading, through a served workload with a commit
    in it."""
    assert not obs.enabled()
    assert obs.span("exec.verdict", lanes=3) is obs.NOOP_SPAN
    assert obs.span("serve.rerun", queries=1) is obs.NOOP_SPAN
    counters = {k: c.value for k, c in obs.metrics.COUNTERS.items()}
    assert len(fused._live_pendings()) == 0
    coal = QueryCoalescer(max_batch=16, pipeline_depth=2)
    tenant = _Tenant("settle", das)
    queries = [_query(i) for i in range(140, 150)]
    group = [(tenant, q, HANDLE, Future()) for q in queries]
    entry = coal._dispatch_group(tenant, HANDLE, group)
    assert len(fused._live_pendings()) == 0     # dispatched, not kept
    _commit(das, 4)
    coal._settle_group(entry)
    answers, _order = _serve(das, queries)
    assert answers == [item[3].result(timeout=60) for item in group]
    assert obs.events() == []
    assert {k: c.value for k, c in obs.metrics.COUNTERS.items()} == counters
    assert fused.programs_in_flight() == 0


# -- the account ------------------------------------------------------------


def _ev(name, start, dur, cpu_ms=None, thread="worker", **attrs):
    if cpu_ms is not None:
        attrs["cpu_ms"] = cpu_ms
    return (name, "X", start, dur, 0, 0, None, thread, attrs)


def test_worker_account_own_wall_and_cpu_of_a_three_level_nest():
    events = [
        _ev("serve.drain", 0.0, 0.5, cpu_ms=1.0, queries=2),
        # settle 1.0 .. 11.0: fetch 1.5 .. 3.5; materialize 4.0 .. 7.0
        # holding dedup 4.5 .. 5.5; format 8.0 .. 9.0
        _ev("serve.settle", 1.0, 10.0, cpu_ms=6000.0, queries=2,
            lock_wait_ms=20.0, resolve_ms=500.0),
        _ev("exec.settle_fetch", 1.5, 2.0, cpu_ms=150.0, wait_ms=1500.0,
            inflight=3),
        _ev("mesh.fetch", 1.5, 2.0, cpu_ms=150.0, wait_ms=1500.0),
        _ev("exec.materialize", 4.0, 3.0, cpu_ms=2000.0),
        _ev("mesh.dedup", 4.5, 1.0, cpu_ms=900.0),
        _ev("exec.format", 8.0, 1.0, cpu_ms=800.0),
        ("serve.answer", "i", 9.5, 0.0, 7, 0, None, "worker", {}),
        ("serve.answer", "i", 9.6, 0.0, 8, 0, None, "worker", {}),
        # another thread's span over the whole stretch names nothing
        _ev("wire.query", 0.0, 12.0, cpu_ms=5.0, thread="grpc-0"),
        # a second settle, outside the window asked for below
        _ev("serve.settle", 20.0, 1.0, cpu_ms=10.0, resolve_ms=1.0),
    ]
    account = obs.worker_account(events, t0=0.0, t1=15.0)
    assert account["thread"] == "worker"
    rows = account["spans"]
    assert "wire.query" not in rows
    settle = rows["serve.settle"]
    assert settle["count"] == 1 and settle["wall_s"] == pytest.approx(10.0)
    # own = 10 - (2 + 3 + 1): direct children only (dedup is the
    # materialize's, mesh.fetch the fetch's)
    assert settle["own_wall_s"] == pytest.approx(4.0)
    assert settle["own_cpu_s"] == pytest.approx(6.0 - 0.15 - 2.0 - 0.8)
    assert settle["attrs"] == {
        "queries": 2, "lock_wait_ms": 20.0, "resolve_ms": 500.0}
    assert rows["exec.materialize"]["own_wall_s"] == pytest.approx(2.0)
    assert rows["exec.materialize"]["own_cpu_s"] == pytest.approx(1.1)
    assert rows["mesh.dedup"]["own_wall_s"] == pytest.approx(1.0)
    # equal intervals nest in recording order: the mesh's span is the
    # innermost, the shared one keeps nothing of its own
    assert rows["exec.settle_fetch"]["own_wall_s"] == pytest.approx(0.0)
    assert rows["exec.settle_fetch"]["own_cpu_s"] == pytest.approx(0.0)
    assert rows["mesh.fetch"]["own_wall_s"] == pytest.approx(2.0)
    # `inflight` is a gauge: its sum over spans means nothing
    assert rows["exec.settle_fetch"]["attrs"] == {"wait_ms": 1500.0}
    assert account["instants"] == {"serve.answer": 2}
    # largest own wall first
    assert list(rows)[0] == "serve.settle"
    # every second of the thread's spans is some span's own
    assert sum(r["own_wall_s"] for r in rows.values()) == pytest.approx(10.5)
    text = obs.account_text(account, per=2)
    assert "serve.settle" in text and "resolve_ms=500" in text
    # the whole ring; a ring without a worker has no account
    assert obs.worker_account(events)["spans"]["serve.settle"]["count"] == 2
    idle = obs.worker_account([e for e in events if e[7] != "worker"])
    assert idle["thread"] is None and idle["spans"] == {}
