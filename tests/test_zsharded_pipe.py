"""Sharded serving parity (ISSUE 3).

Pins, in one place (markers `sharded` + `pipeline`, standalone via
`ops/pytests.sh sharded`):

  * mesh tenants ride the dispatch/settle pipeline: pipelined (depth 2)
    and serial (depth 1) coalescer execution issue IDENTICAL shard_map
    program counts and identical answers on a ShardedDB tenant;
  * a repeated mesh query through the serving path is a pure host dict
    lookup — zero shard_map programs, zero host fetches;
  * the widened ResultCache scope: tree-composite entries (query/tree.py)
    and count-batch entries (query/fused.py count_batch) hit at zero
    device dispatches and invalidate exactly on commit — on TensorDB and
    (tree path) on ShardedDB.

Compile-budget note (ROADMAP tier-1): every query here reuses a handful
of fixed plan shapes on the small animals KB.
"""

import threading
from concurrent.futures import Future

import pytest

from das_tpu.ops import counters
from das_tpu.api.atomspace import DistributedAtomSpace, QueryOutputFormat
from das_tpu.core.config import DasConfig
from das_tpu.models.animals import animals_metta
from das_tpu.query import compiler, fused
from das_tpu.query.ast import And, Link, Node, Not, Or, Variable
from das_tpu.storage.atom_table import load_metta_text
from das_tpu.storage.tensor_db import TensorDB

pytestmark = [pytest.mark.sharded, pytest.mark.pipeline]

#: extends the pair query's answer set: chimp→mammal exists, so the new
#: platypus→chimp edge adds ($1=platypus, $2=chimp) exactly after commit
COMMIT = '(: "platypus" Concept)\n(Inheritance "platypus" "chimp")'


def _pair_query(concept="mammal"):
    return And([
        Link("Inheritance", [Variable("$1"), Variable("$2")], True),
        Link("Inheritance", [Variable("$2"), Node("Concept", concept)], True),
    ])


def _chain_query():
    return And([
        Link("Inheritance", [Variable("$1"), Variable("$2")], True),
        Link("Inheritance", [Variable("$2"), Variable("$3")], True),
    ])


def _neg_query():
    return And([
        Link("Inheritance", [Variable("$1"), Node("Concept", "mammal")], True),
        Not(Link("Inheritance", [Variable("$1"), Node("Concept", "animal")], True)),
    ])


def _sharded_das(config=None):
    from das_tpu.parallel.sharded_db import ShardedDB

    data = load_metta_text(animals_metta())
    db = ShardedDB(data, config or DasConfig())
    return DistributedAtomSpace(database_name="zsp", db=db), db


def _tensor_das(config=None):
    data = load_metta_text(animals_metta())
    db = TensorDB(data, config or DasConfig())
    return DistributedAtomSpace(database_name="zspt", db=db), db


@pytest.fixture(scope="module")
def env():
    """One shared mesh store for the non-mutating tests, so the module
    pays each shard_map compile once."""
    return _sharded_das()


class _FakeTenant:
    def __init__(self, das):
        self.das = das
        self.lock = threading.RLock()


def _drive(coalescer, tenant, queries):
    futs = [
        coalescer.submit(tenant, q, QueryOutputFormat.HANDLE)
        for q in queries
    ]
    return [f.result(timeout=120) for f in futs]


# -- mesh pipeline --------------------------------------------------------


def test_mesh_pipelined_matches_serial_answers_and_program_count(env):
    """The tentpole pin: pipelining the mesh path changes WHEN shard_map
    programs run relative to host settle, never HOW MANY — depth 2 and
    depth 1 issue identical sharded program counts and identical answers
    over distinct groundings (cache off so every query pays the mesh)."""
    from das_tpu.service.coalesce import QueryCoalescer

    das, db = env
    tenant = _FakeTenant(das)
    concepts = ["mammal", "animal", "reptile", "plant"]
    queries = [_pair_query(c) for c in concepts]
    prev = db.config.result_cache_size
    db.config.result_cache_size = 0
    try:
        das.query_many(queries)  # warm compile + caps

        # batches of ONE: a same-shape mesh batch of two is one group
        # program (ISSUE 43), and how a backlog splits into batches is
        # timing
        serial = QueryCoalescer(max_batch=1, pipeline_depth=1)
        counters.reset_dispatch_counts()
        serial_answers = _drive(serial, tenant, queries)
        serial_programs = counters.DISPATCH_COUNTS["sharded"]

        piped = QueryCoalescer(max_batch=1, pipeline_depth=2)
        counters.reset_dispatch_counts()
        piped_answers = _drive(piped, tenant, queries)
        piped_programs = counters.DISPATCH_COUNTS["sharded"]
    finally:
        db.config.result_cache_size = prev

    assert piped_answers == serial_answers
    assert serial_programs == len(concepts)  # cache really was off
    assert piped_programs == serial_programs, (piped_programs, serial_programs)
    # the batch went through the mesh job pipeline, not per-query queries
    assert all(a == das.query(q) for a, q in zip(piped_answers, queries))


def test_mesh_pipeline_inflight_peak_reaches_depth(env):
    """Under a backlog the worker actually keeps mesh batches in flight
    (dispatches N+1 before settling N) — sharded parity of the zpipeline
    pin."""
    from das_tpu.service.coalesce import QueryCoalescer

    das, db = env
    tenant = _FakeTenant(das)
    c = QueryCoalescer(max_batch=1, pipeline_depth=2)
    futs = [
        (c._queue.put((tenant, _pair_query(), QueryOutputFormat.HANDLE, f)), f)[1]
        for f in (Future() for _ in range(8))
    ]
    c._ensure_worker()
    answers = [f.result(timeout=120) for f in futs]
    assert len(set(answers)) == 1
    assert c.stats["inflight_peak"] >= 2, c.stats


def test_mesh_query_many_cache_hit_zero_programs(env):
    """A repeated mesh query through the serving path is a host dict
    lookup: zero shard_map programs, zero host fetches."""
    das, db = env
    q = _pair_query()
    first = das.query_many([q, q])  # one program: in-batch dedup aliases
    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    again = das.query_many([q, q])
    assert again == first
    assert fused.FETCH_COUNTS["n"] == fetches, "mesh cache hit paid a fetch"
    assert counters.DISPATCH_COUNTS["sharded"] == 0, counters.DISPATCH_COUNTS


def test_mesh_commit_invalidates_serving_cache():
    das, db = _sharded_das()
    q = _pair_query()
    before = das.query_many([q])
    version = db.delta_version
    das.load_metta_text(COMMIT)
    assert db.delta_version > version
    after = das.query_many([q])
    assert after != before
    assert after == [das.query(q)]  # post-commit ground truth


# -- widened result-cache scope: tree composites --------------------------


def test_tree_composite_cache_hit_zero_dispatch_tensor():
    """An Or query runs through the generalized tree executor; its cached
    composite tables answer the repeat with zero device programs and zero
    host fetches, and a commit invalidates exactly the stale entry."""
    das, db = _tensor_das()
    q = Or([
        Link("Inheritance", [Variable("$1"), Node("Concept", "mammal")], True),
        Link("Inheritance", [Variable("$1"), Node("Concept", "reptile")], True),
    ])
    first = das.query(q)
    ex = fused.get_executor(db)
    assert ex.tree_results.stats["misses"] >= 1

    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    again = das.query(q)
    assert again == first
    assert fused.FETCH_COUNTS["n"] == fetches, "tree hit paid a host fetch"
    assert sum(counters.DISPATCH_COUNTS.values()) == 0, counters.DISPATCH_COUNTS
    assert ex.tree_results.stats["hits"] >= 1

    # commit invalidation: platypus→mammal lands in the Or's answer set
    das.load_metta_text('(: "platypus" Concept)\n(Inheritance "platypus" "mammal")')
    after = das.query(q)
    assert after != first
    assert db.get_node_handle("Concept", "platypus") in after
    assert ex.tree_results.stats["invalidations"] >= 1


def test_tree_composite_cache_sharded_unordered(env):
    """The mesh tree executor (ShardedTreeOps — incl. the check_vma-shimmed
    replicate path) shares the cache scope: an unordered Similarity probe
    repeats with zero shard_map programs."""
    das, db = env
    q = Link("Similarity", [Variable("$1"), Node("Concept", "human")], False)
    first = das.query(q)
    ex = db.tables._fused_executor
    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    again = das.query(q)
    assert again == first
    assert fused.FETCH_COUNTS["n"] == fetches
    assert sum(counters.DISPATCH_COUNTS.values()) == 0, counters.DISPATCH_COUNTS
    assert ex.tree_results.stats["hits"] >= 1


# -- widened result-cache scope: count batches ----------------------------


def test_count_batch_cache_hit_and_commit_invalidation():
    das, db = _tensor_das()
    ex = fused.get_executor(db)
    plans_list = [
        compiler.plan_query(db, _pair_query(c)) for c in ("mammal", "animal")
    ]
    first = ex.count_batch(plans_list)
    assert all(n is not None for n in first)

    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    again = ex.count_batch(plans_list)
    assert again == first
    assert fused.FETCH_COUNTS["n"] == fetches, "count hit paid a device fetch"
    assert sum(counters.DISPATCH_COUNTS.values()) == 0, counters.DISPATCH_COUNTS

    das.load_metta_text(COMMIT)  # platypus→chimp→mammal: +1 pair
    after = ex.count_batch(
        [compiler.plan_query(db, _pair_query(c)) for c in ("mammal", "animal")]
    )
    assert after[0] == first[0] + 1, (first, after)


def test_miner_count_many_rides_the_caches():
    """The miner's joint counts repeat across the stochastic loop: the
    second count_many answers the non-trivial entries from the cache."""
    from das_tpu.mining.miner import PatternMiner

    das, db = _tensor_das()
    miner = PatternMiner(db)
    queries = [_pair_query("mammal"), _pair_query("animal")]
    first = miner.count_many(queries)
    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    again = miner.count_many(queries)
    assert again == first
    assert fused.FETCH_COUNTS["n"] == fetches
    assert sum(counters.DISPATCH_COUNTS.values()) == 0, counters.DISPATCH_COUNTS


# -- serving stats --------------------------------------------------------


def test_service_stats_surface_sharded_and_tenants(env):
    """coalescer_stats() surfaces the sharded routes and a per-tenant
    breakdown with inflight_peak."""
    from das_tpu.service.server import DasService

    das, db = env
    service = DasService()
    token = service.attach_tenant("zsp_stats", das)
    q = "Node n Concept mammal, Link Inheritance $1 $2, Link Inheritance $2 n, AND"
    for _ in range(3):
        reply = service.query(
            {"key": token, "query": q, "output_format": "HANDLE"}
        )
        assert reply["success"], reply["msg"]
    stats = service.coalescer_stats()
    assert "sharded" in stats["routes"]
    assert stats["routes"]["sharded"] >= 1
    per = stats["tenants"]["zsp_stats"]
    assert per["items"] >= 3
    assert "inflight_peak" in per and "cache_hits" in per
    assert stats["cache_hits"] >= 1  # repeats hit the mesh result cache


# -- async end-to-end serving on the mesh (ISSUE 6) -----------------------


def test_mesh_speculative_dispatch_keeps_program_count(env):
    """Mesh parity of the speculation pin: a depth-3 window dispatching
    groups before earlier settles land issues IDENTICAL shard_map
    program counts to serial, with the speculative dispatches counted.
    Same plan shape as the module's other tests — no new mesh compiles."""
    from das_tpu.service.coalesce import QueryCoalescer

    das, db = env
    tenant = _FakeTenant(das)
    concepts = ["mammal", "animal", "reptile", "plant"]
    queries = [_pair_query(c) for c in concepts]
    prev = db.config.result_cache_size
    db.config.result_cache_size = 0
    try:
        das.query_many(queries)  # warm compile + caps

        serial = QueryCoalescer(max_batch=1, pipeline_depth=1)
        counters.reset_dispatch_counts()
        serial_answers = _drive(serial, tenant, queries)
        serial_programs = counters.DISPATCH_COUNTS["sharded"]

        # pre-queue the backlog so the window actually fills past one
        # unsettled group (speculation), then drain
        spec = QueryCoalescer(
            max_batch=1, pipeline_depth=3, pipeline_depth_max=6
        )
        counters.reset_dispatch_counts()
        futs = []
        for q in queries:
            f = Future()
            spec._queue.put((tenant, q, QueryOutputFormat.HANDLE, f))
            futs.append(f)
        spec._ensure_worker()
        spec_answers = [f.result(timeout=120) for f in futs]
        spec_programs = counters.DISPATCH_COUNTS["sharded"]
    finally:
        db.config.result_cache_size = prev

    assert spec_answers == serial_answers
    assert serial_programs == len(concepts)  # cache really was off
    assert spec_programs == serial_programs, (spec_programs, serial_programs)
    assert spec.stats["speculative_dispatches"] >= 1, spec.stats


def test_mesh_streaming_settle_yields_incrementally(env):
    """Mesh tenants ride the streaming settle: settle_iter yields each
    query's answer as its verdict lands, identical to the blocking
    settle()/query() ground truth."""
    das, db = env
    queries = [_pair_query("mammal"), _pair_query("animal")]
    expected = [das.query(q) for q in queries]
    job = das.query_many_dispatch(queries)
    seen = []
    for i, answer in job.settle_iter():
        assert not isinstance(answer, Exception), answer
        seen.append((i, answer))
    assert len(seen) == len(queries)
    assert [a for _, a in sorted(seen)] == expected


# -- the planner's kept whole-table supports on the mesh store -------------


def test_mesh_table_support_kept_until_a_commit_swaps_its_segments():
    """query/starcount.py `_table_sparse` reaches a ShardedDB through
    `host_segments` like a TensorDB: the support outlives 600 distinct
    grounded supports (the FIFO it used to share), and a commit costs
    one extraction, equal to a cold mesh store's, replacing the entry."""
    from das_tpu import obs
    from das_tpu.query import starcount

    das, db = _sharded_das()
    tid = db._type_id("Inheritance")
    spec = (2, tid, 0, ())

    def by_handle(store, ent):
        (idx, cnt), total = ent
        hexes = store.fin.hex_of_row
        return {hexes[int(r)]: int(c) for r, c in zip(idx, cnt)}, total

    was = obs.enabled()
    obs.configure(enabled=True)
    try:
        built = obs.counter("planner.table_extractions")
        first = starcount._table_sparse(db, spec)
        built0 = built.value
        for r in range(600):
            starcount._host_sparse_deg(db, (2, tid, 1, ((0, r),)))
        assert starcount._table_sparse(db, spec) is first
        assert built.value == built0
        das.load_metta_text(COMMIT)
        after = starcount._table_sparse(db, spec)
        assert starcount._table_sparse(db, spec) is after
        assert built.value == built0 + 1
    finally:
        obs.configure(enabled=was)
    assert after[1] == first[1] + 1
    cold = type(db)(db.data, DasConfig())  # built whole: no overlay
    assert by_handle(db, after) == by_handle(
        cold, starcount._table_sparse(cold, spec)
    )
    assert list(starcount._table_cache(db)) == [spec[:3]]
