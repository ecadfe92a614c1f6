"""Serving pipeline + delta-versioned result cache (ISSUE 2).

Pins, in one place (marker `pipeline`, standalone via
`ops/pytests.sh pipeline`):

  * a result-cache hit issues ZERO device programs and zero host fetches;
  * pipelined coalescer execution (depth 2) issues exactly the same total
    device-program count as serial (depth 1) and identical answers — the
    pipeline changes overlap, never work;
  * cache invalidation across incremental commits: a query answered from
    cache before `intern_delta` reflects the new atoms after the commit,
    on BOTH TensorDB and ShardedDB (the delta_version key);
  * per-query failure isolation: one bad query in a coalesced batch fails
    only its own future;
  * the config knobs (pipeline_depth, result_cache_size) and the serving
    stats surface.

Compile-budget note (ROADMAP tier-1): every query here reuses ONE fused
plan shape on the small animals KB, so the suite costs a handful of XLA
compiles total.
"""

import threading
from concurrent.futures import Future

import pytest

from das_tpu.ops import counters
from das_tpu.api.atomspace import DistributedAtomSpace
from das_tpu.core.config import DasConfig
from das_tpu.models.animals import animals_metta
from das_tpu.query import compiler, fused
from das_tpu.query.ast import And, Link, Node, Variable
from das_tpu.storage.atom_table import load_metta_text
from das_tpu.storage.tensor_db import TensorDB

pytestmark = pytest.mark.pipeline

#: extends _pair_query's answer set: chimp→mammal exists, so the new
#: platypus→chimp edge adds ($1=platypus, $2=chimp) exactly after commit
COMMIT = '(: "platypus" Concept)\n(Inheritance "platypus" "chimp")'


def _pair_query(concept="mammal"):
    return And([
        Link("Inheritance", [Variable("$1"), Variable("$2")], True),
        Link("Inheritance", [Variable("$2"), Node("Concept", concept)], True),
    ])


def _tensor_das(config=None):
    data = load_metta_text(animals_metta())
    db = TensorDB(data, config or DasConfig())
    return DistributedAtomSpace(database_name="zp", db=db), db


def _sharded_das(config=None):
    from das_tpu.parallel.sharded_db import ShardedDB

    data = load_metta_text(animals_metta())
    db = ShardedDB(data, config or DasConfig())
    return DistributedAtomSpace(database_name="zps", db=db), db


# -- result cache ---------------------------------------------------------


def test_cache_hit_issues_zero_device_programs():
    """The acceptance pin: a repeated query through the serving path is a
    pure host dict lookup — no program dispatch, no host transfer."""
    das, db = _tensor_das()
    q = _pair_query()
    first = das.query_many([q, q])  # 1 program: in-batch dedup aliases #2
    ex = fused.get_executor(db)
    assert ex.results.stats["misses"] >= 1

    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    again = das.query_many([q, q])
    assert again == first
    assert fused.FETCH_COUNTS["n"] == fetches, "cache hit paid a host fetch"
    assert counters.DISPATCH_COUNTS["fused"] == 0, counters.DISPATCH_COUNTS
    assert counters.DISPATCH_COUNTS["lowered"] == 0


def test_cache_disabled_by_zero_size():
    das, db = _tensor_das(DasConfig(result_cache_size=0))
    q = _pair_query()
    das.query_many([q, q])
    ex = fused.get_executor(db)
    assert ex.results.stats["hits"] == 0
    counters.reset_dispatch_counts()
    das.query_many([q])
    assert counters.DISPATCH_COUNTS["fused"] >= 1


def test_single_execute_stays_uncached_by_default():
    """The dispatch-count pins rely on bare execute() timing
    the device — the cache must be opt-in there."""
    das, db = _tensor_das()
    plans = compiler.plan_query(db, _pair_query())
    ex = fused.get_executor(db)
    assert ex.execute(plans, count_only=True) is not None
    counters.reset_dispatch_counts()
    assert ex.execute(plans, count_only=True) is not None
    assert counters.DISPATCH_COUNTS["fused"] == 1

    # ... and the opt-in flag caches: second call is dispatch-free
    assert ex.execute(plans, count_only=True, use_cache=True) is not None
    counters.reset_dispatch_counts()
    assert ex.execute(plans, count_only=True, use_cache=True) is not None
    assert counters.DISPATCH_COUNTS["fused"] == 0


def test_cache_invalidation_across_commit_tensor():
    das, db = _tensor_das()
    q = _pair_query()
    # content-addressed handle: computable before the node exists
    platypus = db.get_node_handle("Concept", "platypus")
    before = das.query_many([q, q])
    assert platypus not in before[0]
    version = db.delta_version
    das.load_metta_text(COMMIT)  # incremental commit (intern_delta)
    assert db.delta_version > version
    assert db._delta_total > 0, "commit must have taken the delta path"
    after = das.query_many([q, q])
    assert after != before and platypus in after[0]
    assert after == [das.query(q), das.query(q)]  # uncached ground truth
    ex = fused.get_executor(db)
    assert ex.results.stats["invalidations"] >= 1


def test_cache_invalidation_across_commit_sharded():
    das, db = _sharded_das()
    q = _pair_query()
    a1 = das.query(q)
    assert das.query(q) == a1
    ex = db.tables._fused_executor
    assert ex.results.stats["hits"] >= 1, "sharded repeat must hit"
    version = db.delta_version
    das.load_metta_text(COMMIT)
    assert db.delta_version > version
    a2 = das.query(q)
    assert a2 != a1 and db.get_node_handle("Concept", "platypus") in a2
    # ground truth: a fresh sharded store over the same data agrees
    from das_tpu.parallel.sharded_db import ShardedDB

    fresh = ShardedDB(das.data, config=db.config, mesh=db.mesh)
    fresh_das = DistributedAtomSpace(database_name="zps2", db=fresh)
    assert a2 == fresh_das.query(q)


# -- coalescer pipeline ---------------------------------------------------


class _FakeTenant:
    def __init__(self, das):
        self.das = das
        self.lock = threading.RLock()


def _build_programs_of(das, n_queries: int) -> None:
    """Run a group of `n_queries` pair queries of ANOTHER concept once
    on `das`, so that the programs of that shape are built (a store
    keeps its own) and `_pair_query()` is still no cache hit: a
    dispatch that BUILDS a program does not feed the dispatch EWMA
    (test_a_dispatch_that_builds_a_program_is_no_dispatch_cost)."""
    from das_tpu.api.atomspace import QueryOutputFormat

    job = das.query_many_dispatch(
        [_pair_query("reptile") for _ in range(n_queries)],
        QueryOutputFormat.HANDLE)
    list(job.settle_iter())


def _drive(coalescer, tenant, queries, fmt=None):
    from das_tpu.api.atomspace import QueryOutputFormat

    fmt = fmt or QueryOutputFormat.HANDLE
    futs = [coalescer.submit(tenant, q, fmt) for q in queries]
    return [f.result(timeout=60) for f in futs]


def test_pipelined_matches_serial_answers_and_program_count():
    """Pipelining changes WHEN device programs run relative to host
    settle, never HOW MANY: depth 2 and depth 1 issue identical fused
    program counts and identical answers over the same workload.  Cache
    off so every query really exercises the device; DISTINCT groundings
    so neither in-batch dedup nor batch-formation noise can alias work;
    one warm-up pass first so capacity learning can't skew either arm."""
    from das_tpu.service.coalesce import QueryCoalescer

    das, db = _tensor_das(DasConfig(result_cache_size=0))
    tenant = _FakeTenant(das)

    def grounded(concept):
        return And([
            Link("Inheritance", [Variable("$1"), Variable("$2")], True),
            Link("Inheritance", [Variable("$2"), Node("Concept", concept)], True),
        ])

    concepts = ["mammal", "animal", "reptile", "plant", "dinosaur", "monkey"]
    das.query_many([grounded(c) for c in concepts])  # warm compile + caps

    # batches of ONE: a same-shape batch of two is one group program
    # (ISSUE 30), and how a backlog splits into batches is timing
    serial = QueryCoalescer(max_batch=1, pipeline_depth=1)
    counters.reset_dispatch_counts()
    serial_answers = _drive(serial, tenant, [grounded(c) for c in concepts])
    serial_programs = counters.DISPATCH_COUNTS["fused"]

    piped = QueryCoalescer(max_batch=1, pipeline_depth=2)
    counters.reset_dispatch_counts()
    piped_answers = _drive(piped, tenant, [grounded(c) for c in concepts])
    piped_programs = counters.DISPATCH_COUNTS["fused"]

    assert piped_answers == serial_answers
    assert serial_programs == len(concepts)  # cache really was off
    assert piped_programs == serial_programs, (piped_programs, serial_programs)


def test_pipeline_inflight_peak_reaches_depth():
    """Under a backlog the worker must actually run batches in flight
    concurrently (dispatch N+1 before settling N)."""
    from das_tpu.service.coalesce import QueryCoalescer
    from das_tpu.api.atomspace import QueryOutputFormat

    das, db = _tensor_das(DasConfig(result_cache_size=0))
    tenant = _FakeTenant(das)
    c = QueryCoalescer(max_batch=1, pipeline_depth=2)
    # enqueue a backlog BEFORE the worker starts so the window can fill
    futs = [
        (c._queue.put((tenant, _pair_query(), QueryOutputFormat.HANDLE, f)), f)[1]
        for f in (Future() for _ in range(8))
    ]
    c._ensure_worker()
    answers = [f.result(timeout=60) for f in futs]
    assert len(set(answers)) == 1
    assert c.stats["inflight_peak"] >= 2, c.stats
    assert c.stats["pipeline_depth"] == 2


def test_commit_between_dispatch_and_settle_rerouted():
    """A commit landing between a batch's dispatch and its settle may
    re-intern global row ids (a FULL re-finalize moves every link row):
    settle must drop the pre-commit dispatched round and re-answer on the
    post-commit store instead of materializing stale rows."""
    # threshold 0 forces every commit onto the FULL re-finalize path —
    # the worst case, where row ids actually move
    das, db = _tensor_das(DasConfig(delta_merge_threshold=0))
    q = _pair_query()
    expected_before = das.query(q)
    job = das.query_many_dispatch([q, q])   # dispatched, not settled
    das.load_metta_text(COMMIT)             # FULL refresh races in
    out = job.settle()
    expected_after = das.query(q)
    assert expected_after != expected_before
    assert out == [expected_after, expected_after]

    # ... and a settle with NO intervening commit keeps the fast path
    job2 = das.query_many_dispatch([q])
    assert job2.settle() == [expected_after]


def test_multi_tenant_batch_honors_pipeline_depth():
    """A drained batch that splits into several (tenant, fmt) groups must
    not overshoot the configured in-flight bound: extra groups wait
    undispatched."""
    from das_tpu.service.coalesce import QueryCoalescer
    from das_tpu.api.atomspace import QueryOutputFormat

    das, db = _tensor_das(DasConfig(result_cache_size=0))
    tenants = [_FakeTenant(das), _FakeTenant(das), _FakeTenant(das)]
    c = QueryCoalescer(max_batch=16, pipeline_depth=1)
    fmt = QueryOutputFormat.HANDLE
    futs = []
    for t in tenants:  # one backlog batch spanning three tenant groups
        for _ in range(2):
            f = Future()
            c._queue.put((t, _pair_query(), fmt, f))
            futs.append(f)
    c._ensure_worker()
    answers = [f.result(timeout=60) for f in futs]
    assert len(set(answers)) == 1
    assert c.stats["inflight_peak"] == 1, c.stats


def test_per_query_failure_isolated_to_its_future():
    """One bad query in a coalesced batch fails only its own future —
    batch-mates keep their answers (the _run_group-granularity swallow is
    gone)."""
    from das_tpu.service.coalesce import QueryCoalescer
    from das_tpu.api.atomspace import QueryOutputFormat

    class Boom:
        """Unplannable (falls to the host path) and then explodes."""

        def matched(self, db, answer):
            raise RuntimeError("poisoned query")

    das, db = _tensor_das()
    tenant = _FakeTenant(das)
    good = _pair_query()
    expected = das.query(good)
    c = QueryCoalescer(max_batch=3, pipeline_depth=1)
    fmt = QueryOutputFormat.HANDLE
    group = [
        (tenant, good, fmt, Future()),
        (tenant, Boom(), fmt, Future()),
        (tenant, good, fmt, Future()),
    ]
    entry = c._dispatch_group(tenant, fmt, group)
    c._settle_group(entry)
    assert group[0][3].result(timeout=5) == expected
    assert group[2][3].result(timeout=5) == expected
    with pytest.raises(RuntimeError, match="poisoned"):
        group[1][3].result(timeout=5)


def test_knobs_flow_from_config_and_env(monkeypatch):
    from das_tpu.service.coalesce import QueryCoalescer

    # dataclass defaults are the deployment defaults
    assert QueryCoalescer().pipeline_depth == DasConfig.pipeline_depth
    assert QueryCoalescer().pipeline_depth_max == DasConfig.pipeline_depth_max
    assert QueryCoalescer().queue_max == DasConfig.coalesce_queue_max
    assert QueryCoalescer(pipeline_depth=1).pipeline_depth == 1
    assert QueryCoalescer(pipeline_depth=0).pipeline_depth == 1  # clamped
    # the ceiling can never sit below the floor
    c = QueryCoalescer(pipeline_depth=5, pipeline_depth_max=2)
    assert c.pipeline_depth_max == 5

    monkeypatch.setenv("DAS_TPU_PIPELINE_DEPTH", "5")
    monkeypatch.setenv("DAS_TPU_PIPELINE_DEPTH_MAX", "11")
    monkeypatch.setenv("DAS_TPU_COALESCE_QUEUE_MAX", "33")
    monkeypatch.setenv("DAS_TPU_RESULT_CACHE", "17")
    cfg = DasConfig.from_env()
    assert cfg.pipeline_depth == 5
    assert cfg.pipeline_depth_max == 11
    assert cfg.coalesce_queue_max == 33
    assert cfg.result_cache_size == 17


def test_serving_stats_surface():
    """coalescer_stats() exposes the whole pipeline: batch counters,
    in-flight peak, the adaptive-window observables (ISSUE 6), cache
    hit/miss, and route counters."""
    from das_tpu.service.server import DasService

    das, db = _tensor_das()
    service = DasService()
    token = service.attach_tenant("zp_stats", das)
    q = "Node n Concept mammal, Link Inheritance $1 $2, Link Inheritance $2 n, AND"
    for _ in range(3):
        reply = service.query(
            {"key": token, "query": q, "output_format": "HANDLE"}
        )
        assert reply["success"], reply["msg"]
    stats = service.coalescer_stats()
    for key in (
        "batches", "items", "max_batch", "max_batch_limit",
        "pipeline_depth", "pipeline_depth_max", "effective_depth",
        "rtt_ewma_ms", "inflight_peak",
        "speculative_dispatches", "early_settles", "queue_rejections",
        "cache_hits", "cache_misses", "cache_invalidations", "routes",
    ):
        assert key in stats, key
    assert stats["items"] >= 3
    assert stats["cache_hits"] >= 1, stats  # repeats hit the result cache
    assert stats["pipeline_depth"] == das.config.pipeline_depth
    assert stats["effective_depth"] >= das.config.pipeline_depth
    assert stats["rtt_ewma_ms"] > 0.0  # settles actually fed the EWMA


# -- async end-to-end serving (ISSUE 6) -----------------------------------


def test_adaptive_depth_math():
    """The window-sizing formula: ceil(rtt / dispatch_cost) clamped to
    [pipeline_depth floor, pipeline_depth_max]; no samples → the floor;
    an explicit serial coalescer (depth 1) never adapts upward."""
    from das_tpu.service.coalesce import QueryCoalescer

    f = QueryCoalescer._depth_from
    assert f(0.0, 0.0, 2, 8) == 2        # no samples yet: the floor
    assert f(100.0, 30.0, 2, 8) == 4     # ceil(100/30)
    assert f(100.0, 1.0, 2, 8) == 8      # wants 100, clamped to the cap
    assert f(1.0, 5.0, 2, 8) == 2        # local dispatch: floor holds
    serial = QueryCoalescer(max_batch=1, pipeline_depth=1)
    serial.stats["rtt_ewma_ms"] = 500.0
    serial.stats["dispatch_ewma_ms"] = 1.0
    assert serial._effective_depth() == 1

    adaptive = QueryCoalescer(
        max_batch=1, pipeline_depth=2, pipeline_depth_max=6
    )
    adaptive.stats["rtt_ewma_ms"] = 90.0
    adaptive.stats["dispatch_ewma_ms"] = 10.0
    assert adaptive._effective_depth() == 6  # ceil(9) clamped to the cap
    adaptive.stats["rtt_ewma_ms"] = 45.0
    assert adaptive._effective_depth() == 5  # ceil(45/10) inside the band
    assert adaptive.stats["effective_depth"] == 5  # surfaced


def test_speculative_pipeline_matches_serial_program_count():
    """pipelined+SPECULATIVE == serial total program counts: a window
    deeper than one unsettled group changes WHEN dispatches happen
    relative to earlier settles, never HOW MANY programs run — and the
    dispatches issued past the first unsettled group are counted."""
    from das_tpu.api.atomspace import QueryOutputFormat
    from das_tpu.service.coalesce import QueryCoalescer

    das, db = _tensor_das(DasConfig(result_cache_size=0))
    tenant = _FakeTenant(das)

    def grounded(concept):
        return And([
            Link("Inheritance", [Variable("$1"), Variable("$2")], True),
            Link("Inheritance", [Variable("$2"), Node("Concept", concept)], True),
        ])

    concepts = ["mammal", "animal", "reptile", "plant", "dinosaur", "monkey"]
    das.query_many([grounded(c) for c in concepts])  # warm compile + caps

    serial = QueryCoalescer(max_batch=1, pipeline_depth=1)
    counters.reset_dispatch_counts()
    serial_answers = _drive(serial, tenant, [grounded(c) for c in concepts])
    serial_programs = counters.DISPATCH_COUNTS["fused"]

    # pre-queue the whole backlog so the depth-3 window actually fills
    # (submissions racing the worker could otherwise keep it starved)
    spec = QueryCoalescer(
        max_batch=1, pipeline_depth=3, pipeline_depth_max=6
    )
    counters.reset_dispatch_counts()
    futs = []
    for c in concepts:
        f = Future()
        spec._queue.put((tenant, grounded(c), QueryOutputFormat.HANDLE, f))
        futs.append(f)
    spec._ensure_worker()
    spec_answers = [f.result(timeout=60) for f in futs]
    spec_programs = counters.DISPATCH_COUNTS["fused"]

    assert spec_answers == serial_answers
    assert serial_programs == len(concepts)  # cache really was off
    assert spec_programs == serial_programs, (spec_programs, serial_programs)
    assert spec.stats["speculative_dispatches"] >= 1, spec.stats
    assert spec.stats["inflight_peak"] >= 3, spec.stats


def test_per_tenant_settle_order_preserved_under_speculation():
    """Settles stay FIFO however deep the window runs: a tenant's
    futures complete in dispatch order (max_batch=1 → one group per
    query, so completion order IS per-tenant settle order)."""
    from das_tpu.api.atomspace import QueryOutputFormat
    from das_tpu.service.coalesce import QueryCoalescer

    das, db = _tensor_das(DasConfig(result_cache_size=0))
    tenant = _FakeTenant(das)
    c = QueryCoalescer(max_batch=1, pipeline_depth=4, pipeline_depth_max=8)
    order = []
    futs = []
    for n in range(6):
        f = Future()
        f.add_done_callback(lambda _f, n=n: order.append(n))
        c._queue.put((tenant, _pair_query(), QueryOutputFormat.HANDLE, f))
        futs.append(f)
    c._ensure_worker()
    answers = [f.result(timeout=60) for f in futs]
    assert len(set(answers)) == 1
    assert order == sorted(order), order


def test_commit_race_invalidation_under_speculation():
    """Two groups dispatched back-to-back — the second SPECULATIVE (the
    first never settled) — then a commit lands: each group's settle
    re-checks its dispatch-time delta version and re-answers on the
    post-commit store, however deep the window ran."""
    das, db = _tensor_das()
    q = _pair_query()
    platypus = db.get_node_handle("Concept", "platypus")
    before = das.query(q)
    job1 = das.query_many_dispatch([q, q])   # dispatched, not settled
    job2 = das.query_many_dispatch([q])      # speculative second group
    das.load_metta_text(COMMIT)              # commit races both windows
    expected = das.query(q)
    assert expected != before and platypus in expected
    assert job1.settle() == [expected, expected]
    assert job2.settle() == [expected]


def test_commit_mid_stream_invalidates_remaining_yields():
    """The PER-YIELD delta_version re-check: streaming paces settle to
    the consumer, so a commit can land BETWEEN yields — every entry not
    yet materialized must re-run on the post-commit store (the answers
    already yielded were consistent when they were delivered)."""
    das, db = _tensor_das()
    q = _pair_query()
    platypus = db.get_node_handle("Concept", "platypus")
    before = das.query(q)
    job = das.query_many_dispatch([q, q])
    it = job.settle_iter()
    first = next(it)                 # answered on the pre-commit store
    assert first == (0, before)
    das.load_metta_text(COMMIT)      # commit lands mid-stream
    expected = das.query(q)
    assert expected != before and platypus in expected
    assert dict(it) == {1: expected}


def test_fallback_only_groups_do_not_feed_rtt_ewma():
    """The rtt EWMA sizes the window from the STREAMED settle wait only.
    A group that degrades to the serial per-query fallback (dispatch
    failed, job=None) is host CPU work the single worker thread cannot
    overlap — feeding it into the estimator would deepen the window
    exactly when speculation buys nothing."""
    from das_tpu.api.atomspace import QueryOutputFormat
    from das_tpu.service.coalesce import QueryCoalescer

    das, db = _tensor_das()
    expected = das.query(_pair_query())
    tenant = _FakeTenant(das)

    def boom(*args, **kwargs):
        raise RuntimeError("no batched dispatch")

    das.query_many_dispatch = boom   # instance attr shadows the method
    c = QueryCoalescer(max_batch=4, pipeline_depth=2)
    fut = c.submit(tenant, _pair_query(), QueryOutputFormat.HANDLE)
    assert fut.result(timeout=60) == expected
    snap = c.snapshot()
    assert snap["rtt_ewma_ms"] == 0.0
    assert snap["dispatch_ewma_ms"] == 0.0  # no device enqueue happened
    assert snap["effective_depth"] == c.pipeline_depth


def test_early_settle_streams_before_group_completes():
    """The early-settle pin: settle_iter yields the fused-answered
    query's rows BEFORE the group's host-fallback member has even run —
    first rows one settle after the client's own dispatch, not after the
    whole group resolves."""
    from das_tpu.api.atomspace import QueryOutputFormat

    das, db = _tensor_das()

    class HostOnly:
        """Unplannable: resolves via the per-query dispatcher."""

        def matched(self, db_, answer):
            return False

    good = _pair_query()
    expected = das.query(good)
    calls = {"n": 0}
    real_query = das.query

    def counting_query(query, fmt=QueryOutputFormat.HANDLE):
        calls["n"] += 1
        return real_query(query, fmt)

    das.query = counting_query  # instance attr shadows the method
    try:
        job = das.query_many_dispatch([good, HostOnly()])
        it = job.settle_iter()
        first = next(it)
        assert first == (0, expected)
        assert calls["n"] == 0, "first rows must precede the fallback"
        rest = list(it)
    finally:
        del das.query
    assert [i for i, _ in rest] == [1]
    assert calls["n"] == 1  # exactly the host-fallback member


def test_early_settles_counted_for_wide_groups():
    """A streamed wide group counts every answer delivered before its
    group finished (all but the last), and the settle EWMA moves."""
    from das_tpu.api.atomspace import QueryOutputFormat
    from das_tpu.service.coalesce import QueryCoalescer

    das, db = _tensor_das()
    _build_programs_of(das, 3)
    tenant = _FakeTenant(das)
    c = QueryCoalescer(max_batch=4, pipeline_depth=1)
    fmt = QueryOutputFormat.HANDLE
    group = [(tenant, _pair_query(), fmt, Future()) for _ in range(3)]
    entry = c._dispatch_group(tenant, fmt, group)
    c._settle_group(entry)
    answers = [item[3].result(timeout=10) for item in group]
    assert len(set(answers)) == 1
    assert c.stats["early_settles"] == 2, c.stats
    assert c.stats["rtt_ewma_ms"] > 0.0
    assert c.stats["dispatch_ewma_ms"] > 0.0


def test_cache_hit_groups_do_not_feed_rtt_ewma():
    """The rtt estimator is fed the timed host TRANSFER only
    (settle_pending_iter times jax.device_get → job.settle_rtt_ms).  An
    all-hit group performs no fetch — reading its sub-ms streamed yields
    as the settle round-trip would collapse the adaptive window to the
    floor exactly on the hot cached workload — so it must leave the
    estimator untouched."""
    from das_tpu.api.atomspace import QueryOutputFormat
    from das_tpu.service.coalesce import QueryCoalescer

    das, db = _tensor_das()
    _build_programs_of(das, 1)
    tenant = _FakeTenant(das)
    c = QueryCoalescer(max_batch=4, pipeline_depth=2)
    fmt = QueryOutputFormat.HANDLE
    # first group: a real fetch populates the cache and feeds the EWMAs
    group = [(tenant, _pair_query(), fmt, Future())]
    c._settle_group(c._dispatch_group(tenant, fmt, group))
    first_answer = group[0][3].result(timeout=10)
    rtt_after_fetch = c.stats["rtt_ewma_ms"]
    dispatch_after_enqueue = c.stats["dispatch_ewma_ms"]
    assert rtt_after_fetch > 0.0
    assert dispatch_after_enqueue > 0.0
    # second group: pure cache hit, zero fetches, zero device enqueues —
    # NEITHER estimator may move toward the sub-ms hit latency (rtt
    # collapsing floors the window; dispatch collapsing pegs it at the
    # ceiling — both mis-size it on the hot cached workload)
    hit = [(tenant, _pair_query(), fmt, Future())]
    entry = c._dispatch_group(tenant, fmt, hit)
    c._settle_group(entry)
    assert hit[0][3].result(timeout=10) == first_answer
    assert entry[3].settle_rtt_ms is None, "all-hit group fetched nothing"
    assert c.stats["rtt_ewma_ms"] == rtt_after_fetch
    assert c.stats["dispatch_ewma_ms"] == dispatch_after_enqueue
    assert c.stats["early_settles"] == 0  # lone answers are never early


def test_a_dispatch_that_builds_a_program_is_no_dispatch_cost():
    """The dispatch EWMA is the host cost of ONE window slot.  A
    dispatch that builds a program (the first of a shape; 40 s on the
    chip for a whole-store join, PERF.md §6 PR 44) spends its time in
    the compiler, or in the persistent cache's load: fed to the
    estimator it holds `ceil(rtt / dispatch)` at the floor for the next
    dozen dispatches, and one system serves two ways depending on what
    its compile cache held.  Such a dispatch feeds nothing; the next
    one of the same shape, which builds nothing, does."""
    from das_tpu.api.atomspace import QueryOutputFormat
    from das_tpu.service import coalesce
    from das_tpu.service.coalesce import QueryCoalescer

    fmt = QueryOutputFormat.HANDLE
    das, _db = _tensor_das()        # a store builds its own programs
    tenant = _FakeTenant(das)
    c = QueryCoalescer(max_batch=4, pipeline_depth=2)
    for concept, builds in (("mammal", True), ("reptile", False)):
        # the same shape twice, the concept a traced key: the second
        # is neither a result-cache hit nor a new program
        group = [(tenant, _pair_query(concept), fmt, Future())]
        before = coalesce._compiles_here()
        c._settle_group(c._dispatch_group(tenant, fmt, group))
        assert isinstance(group[0][3].result(timeout=60), str)
        assert (coalesce._compiles_here() > before) == builds
        assert (c.stats["dispatch_ewma_ms"] == 0.0) == builds, c.stats


def test_cancelled_futures_do_not_count_as_early_settles():
    """Counter honesty: a client cancelling its future mid-settle still
    gets a yield from settle_iter, but nothing was DELIVERED — streamed
    and early_settles must only credit answers that actually reached a
    client."""
    from das_tpu.api.atomspace import QueryOutputFormat
    from das_tpu.service.coalesce import QueryCoalescer

    das, db = _tensor_das()
    tenant = _FakeTenant(das)
    c = QueryCoalescer(max_batch=4, pipeline_depth=1)
    fmt = QueryOutputFormat.HANDLE
    group = [(tenant, _pair_query(), fmt, Future()) for _ in range(3)]
    entry = c._dispatch_group(tenant, fmt, group)
    assert group[0][3].cancel()      # client walks away mid-settle
    c._settle_group(entry)
    answers = [item[3].result(timeout=10) for item in group[1:]]
    assert len(set(answers)) == 1
    # 2 delivered, the last not early: 1 — NOT 2 (the cancelled yield)
    assert c.stats["early_settles"] == 1, c.stats
    # ... but when the CANCELLED yield comes last, the group kept
    # working after the final delivery, so both deliveries were early
    group2 = [(tenant, _pair_query(), fmt, Future()) for _ in range(3)]
    entry2 = c._dispatch_group(tenant, fmt, group2)
    assert group2[2][3].cancel()
    c._settle_group(entry2)
    assert group2[0][3].result(timeout=10) == answers[0]
    assert c.stats["early_settles"] == 1 + 2, c.stats


def test_settle_rtt_recorded_eagerly_mid_stream():
    """The settle round-trip is recorded at the FIRST post-fetch yield,
    not after the stream completes — a mid-stream failure abandoning the
    iterator must not drop the genuine wire sample (the estimator would
    hold a persistently-failing tenant at the floor despite a real
    ~100 ms wire)."""
    das, db = _tensor_das()
    job = das.query_many_dispatch([_pair_query()])
    it = job.settle_iter()
    next(it)                        # first post-fetch answer lands
    assert job.settle_rtt_ms is not None and job.settle_rtt_ms > 0.0
    sample = job.settle_rtt_ms
    it.close()                      # abandon mid-stream: sample survives
    assert job.settle_rtt_ms == sample


def test_commit_raced_groups_do_not_feed_rtt_ewma():
    """A commit landing between dispatch and settle drops the round to
    the per-query re-run path — host work with no fetch; the estimator
    must see None, not the re-run's compile+materialize time (which
    would peg effective_depth at the ceiling exactly when deeper
    speculation buys nothing)."""
    das, db = _tensor_das()
    platypus = db.get_node_handle("Concept", "platypus")
    job = das.query_many_dispatch([_pair_query()])
    das.load_metta_text(COMMIT)          # race: commit before settle
    answers = dict(job.settle_iter())    # re-answered post-commit
    assert platypus in answers[0]
    assert job.settle_rtt_ms is None


def test_early_settles_count_streams_before_fallback_resolutions():
    """A mid-stream settle failure hands the unresolved remainder to the
    per-query fallback loop — every answer that DID stream reached its
    client before the group finished, so all of them count as early
    (not streamed-minus-one, which undercounts exactly the mixed
    streamed+fallback groups where early delivery matters)."""
    from das_tpu.api.atomspace import QueryOutputFormat
    from das_tpu.service.coalesce import QueryCoalescer

    das, db = _tensor_das()
    expected = das.query(_pair_query())
    tenant = _FakeTenant(das)
    c = QueryCoalescer(max_batch=4, pipeline_depth=2)
    fmt = QueryOutputFormat.HANDLE
    group = [(tenant, _pair_query(), fmt, Future()) for _ in range(2)]

    class _OneThenBoom:
        """Streams the first answer, then dies: the second future must
        resolve via the coalescer's per-query fallback."""

        def settle_iter(self):
            yield 0, expected
            raise RuntimeError("stream died mid-group")

    c._settle_group((tenant, fmt, group, _OneThenBoom()))
    assert group[0][3].result(timeout=10) == expected
    assert group[1][3].result(timeout=10) == expected
    assert c.stats["early_settles"] == 1, c.stats


def test_queue_backpressure_rejects_beyond_bound():
    """Past coalesce_queue_max the submit queue REJECTS with an error
    future instead of growing host memory with the open-loop client
    count; rejections are counted."""
    from das_tpu.core.exceptions import CoalescerSaturatedError
    from das_tpu.service.coalesce import QueryCoalescer

    c = QueryCoalescer(max_batch=4, pipeline_depth=2, queue_max=2)
    # fill to the bound WITHOUT spawning the worker (submit would drain)
    c._queue.put_nowait((None, None, None, Future()))
    c._queue.put_nowait((None, None, None, Future()))
    fut = c.submit(None, _pair_query(), None)
    with pytest.raises(CoalescerSaturatedError):
        fut.result(timeout=5)
    assert c.snapshot()["queue_rejections"] == 1
    assert c._worker is None, "a rejected submit must not spawn the worker"
    # 0 = unbounded: the pre-bound behavior survives
    unbounded = QueryCoalescer(max_batch=4, pipeline_depth=2, queue_max=0)
    assert unbounded._queue.maxsize == 0
