"""The mesh job rides with its group (ISSUE 43).

`_ShardedExecJob` offers the group hooks `_ExecJob` has had since ISSUE
30 (query/fused.py _GroupHooks, ONE body for both), so the shared
dispatch loop enqueues the same-signature mesh jobs of a batch as ONE
`das_sharded_group` program of `GROUP_LANES` lanes, `lanes_program`
INSIDE the `shard_map`, and the settle loop hands every job its lane
of the one fetched block.  Pinned here, on the suite's CPU mesh of 4:
the answers are the per-query `execute` answers row for row; programs
enqueued == signature groups and one FETCH_COUNTS tick a round; a
hash-partitioned join in a group; a lane over its capacity retries
alone (two as a group), `mesh.retries` one per JOB; the reseed verdict,
the commit guard, a lane's on-demand device references, and what a
group program's collectives are said to move.

Compile budget: test_group_dispatch.py's store (83 genes) and its two
query shapes on 4 shards: a lone and a group program each, and their
retry tiers."""

import numpy as np
import pytest

from das_tpu import obs
from das_tpu.core.config import DasConfig
from das_tpu.ops import counters
from das_tpu.parallel import fused_sharded as fs
from das_tpu.parallel.mesh import make_mesh
from das_tpu.parallel.sharded_db import ShardedDB
from das_tpu.query import compiler, fused
from das_tpu.storage.atom_table import load_metta_text

from tests.test_group_dispatch import (
    HUB, HUB2, N_PROCS, PER_PROC, _mixed, _plans, _rows, _store_text,
    grounded3, shared2,
)

S = 4


def _db(**config):
    # the greedy seeds: every gene's first capacities are the same, so a
    # hub's overflow is met at settle and not planned around
    config.setdefault("use_planner", "off")
    return ShardedDB(load_metta_text(_store_text()), DasConfig(**config),
                     mesh=make_mesh(S))


@pytest.fixture(autouse=True)
def _cold_cap_store(monkeypatch):
    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")


@pytest.fixture(scope="module")
def db():
    return _db(result_cache_size=0)


def _tight(ex, cap=16):
    """Seed the join capacities of a shape nobody has settled yet at
    `cap` rows a shard: a gene's 44 pairs (11 a shard) fit, a hub's 176
    (44 a shard) ask for 64.  On 4 shards the greedy seed of 64 a shard
    holds the hub too."""
    build = ex._exec_job

    def exec_job(plans, count_only):
        job = build(plans, count_only)
        if job.sigs not in ex._caps:
            job.join_caps = tuple(cap for _ in job.join_caps)
        return job

    ex._exec_job = exec_job


@pytest.fixture()
def traced():
    obs.configure(enabled=True)
    obs.reset()
    yield
    obs.configure(enabled=False)
    obs.reset()


def test_both_job_types_share_the_group_hooks():
    """ONE body: the hooks are query/fused.py _GroupHooks' on both job
    classes; a tree job has none and is enqueued by its own dispatch."""
    for cls in (fused._ExecJob, fs._ShardedExecJob):
        assert cls.dispatch_group is fused._GroupHooks.dispatch_group
        assert cls.lane_out is fused._GroupHooks.lane_out
    for cls in (fs._ShardedTreeExecJob, fused._TreeExecJob):
        assert not hasattr(cls, "dispatch_group")
    assert issubclass(fs.ShardedFusedResult, fused.FusedResult)


@pytest.mark.parametrize("n", [2, 5, 32, 40])
def test_mesh_group_answers_equal_per_query_execute(db, n):
    ex = fs.get_sharded_executor(db)
    plans = _plans(db, _mixed(n))
    want = [ex.execute(p) for p in plans]
    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    pending = ex.dispatch_many(plans)
    # programs enqueued == signature groups: one per shape (a shape
    # asked for once runs alone; one wider than the top rung is cut
    # there), never one per query
    keys = {fused.ResultCache.key(p, False): len(p) for p in plans}
    per_shape = [list(keys.values()).count(k) for k in set(keys.values())]
    programs = sum(-(-k // fused.GROUP_LANES) for k in per_shape)
    assert len(pending.programs) == programs <= 3
    assert counters.DISPATCH_COUNTS["sharded"] == programs
    assert sum(len(m) for m, _ in pending.programs) == len(keys)
    got = dict(ex.settle_many_iter(pending))
    assert fused.FETCH_COUNTS["n"] == fetches + 1   # ONE transfer a round
    assert counters.DISPATCH_COUNTS["sharded"] == programs   # no retry
    assert sorted(got) == list(range(n))
    for i, ref in enumerate(want):
        assert got[i].count == ref.count
        assert got[i].var_names == ref.var_names
        assert got[i].reseed_needed == ref.reseed_needed
        # row for row AND shard for shard: a lane is the lone program's
        # [S, cap, k] output
        assert np.array_equal(got[i].host_valid, ref.host_valid)
        assert np.array_equal(got[i].host_vals[got[i].host_valid],
                              ref.host_vals[ref.host_valid])
    if n >= 3:
        assert got[n - 1] is got[0]   # the duplicate aliases ONE result


def test_mesh_count_only_group(db):
    ex = fs.get_sharded_executor(db)
    plans = _plans(db, [grounded3(f"g{i}") for i in range(5)])
    want = [ex.execute(p, count_only=True).count for p in plans]
    pending = ex.dispatch_many(plans, count_only=True)
    assert len(pending.programs) == 1
    (_members, out), = pending.programs
    assert out.shape[0] == fused.GROUP_LANES        # the stats alone
    got = ex.settle_many(pending)
    assert [r.count for r in got] == want
    assert all(r.vals is None and r.host_vals is None for r in got)
    assert {k[1] for k in ex._group_cache} >= {True}


def test_lone_mesh_job_runs_the_das_sharded_entry(db):
    """A job alone in its signature calls today's program: the same
    object from the same cache, and no group program is built for it."""
    ex = fs.get_sharded_executor(db)
    plans = _plans(db, [shared2("g7")])
    ex.execute(plans[0])
    entries, groups = dict(ex._cache), dict(ex._group_cache)
    job = ex._exec_job(plans[0], False)
    assert (job.plan_sig(), False) in entries
    assert job.plan_sig() is job.plan_sig()     # ONE object a capacity step
    got = ex.execute_many(plans)
    assert got[0].count == ex.execute(plans[0]).count
    assert ex._cache == entries and ex._group_cache == groups


def test_mesh_group_program_is_named_and_built_once(db):
    ex = fs.get_sharded_executor(db)
    ex.execute_many(_plans(db, [shared2(f"g{i}") for i in range(3)]))
    built = len(ex._group_cache)
    ex.execute_many(_plans(db, [shared2(f"g{i}") for i in range(10, 27)]))
    assert len(ex._group_cache) == built       # pads to the same lanes
    assert {key[2] for key in ex._group_cache} == {fused.GROUP_LANES}
    # the hoisted slot: every lane's `Member $2 $3` has one key
    assert any(None in key[3] for key in ex._group_cache)
    for (sig, count_only, *_), (program, _names) in ex._group_cache.items():
        assert isinstance(program, fs._MeshProgram)
        name = "das_sharded_group" + ("_count" if count_only else "")
        assert program.fn.__wrapped__.__name__ == name


def test_hash_partitioned_join_in_a_group_equals_alone():
    """broadcast_limit 0: every moved join hash-partitions (`exch_caps`
    > 0, two all_to_all a join), alone and under the lanes axis."""
    db = _db(result_cache_size=0)
    ex = fs.get_sharded_executor(db)
    ex.broadcast_limit = 0
    genes = ["g1", "g2", HUB, "g3"]
    plans = _plans(db, [grounded3(g) for g in genes])
    job = ex._exec_job(plans[0], False)
    assert any(q > 0 for q in job.exch_caps)
    want = [ex.execute(p) for p in plans]
    got = ex.execute_many(plans)
    assert any(len(k[0].exch_caps) and max(k[0].exch_caps) > 0
               for k in ex._group_cache)
    for g, w in zip(got, want):
        assert (g.count, g.reseed_needed) == (w.count, w.reseed_needed)
        assert _rows(g) == _rows(w)
    assert max(r.count for r in got) > 0


def test_overflowing_mesh_lane_retries_alone(traced):
    """The hub's join is past the capacity its group was seeded with: its
    lane asks for more and re-dispatches ALONE in round two; its
    group-mates are yielded in round one; `mesh.retries` counts the one
    JOB that went again."""
    db = _db(result_cache_size=0)
    ex = fs.get_sharded_executor(db)
    _tight(ex)
    genes = ["g1", "g2", HUB, "g3", "g4"]
    plans = _plans(db, [shared2(g) for g in genes])
    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    pending = ex.dispatch_many(plans)
    assert [len(m) for m, _ in pending.programs] == [5]
    stream = ex.settle_many_iter(pending)
    first = [next(stream) for _ in range(4)]
    assert sorted(i for i, _ in first) == [0, 1, 3, 4]
    assert fused.FETCH_COUNTS["n"] == fetches + 1     # still round one
    assert counters.DISPATCH_COUNTS["sharded"] == 1
    assert obs.counter("mesh.retries").value == 0
    (i, hub), = list(stream)
    assert i == 2
    assert fused.FETCH_COUNTS["n"] == fetches + 2
    assert counters.DISPATCH_COUNTS["sharded"] == 2      # the hub, alone
    assert obs.counter("mesh.retries").value == 1
    assert hub.count == N_PROCS * PER_PROC
    assert [r.count for _, r in first] == [2 * PER_PROC] * 4
    # programs and lanes, as the shared loop counts them
    assert obs.counter("exec.group_programs").value == 2
    assert obs.counter("exec.group_lanes").value == 5 + 1
    # the capacities it learned seed the next group: no retry
    fetches = fused.FETCH_COUNTS["n"]
    again = ex.execute_many(plans)
    assert fused.FETCH_COUNTS["n"] == fetches + 1
    assert [r.count for r in again] == [2 * PER_PROC] * 2 + [hub.count] + [
        2 * PER_PROC] * 2


def test_two_overflowing_mesh_lanes_retry_as_a_group(traced):
    """Lanes that ask for the same new capacities share their retry:
    round two is ONE program of two lanes, and each of the two jobs is
    a counted retry."""
    db = _db(result_cache_size=0)
    ex = fs.get_sharded_executor(db)
    _tight(ex)
    plans = _plans(db, [shared2(g) for g in ("g1", HUB, "g2", HUB2)])
    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    got = ex.execute_many(plans)
    assert [r.count for r in got] == [2 * PER_PROC, N_PROCS * PER_PROC] * 2
    assert counters.DISPATCH_COUNTS["sharded"] == 2
    assert fused.FETCH_COUNTS["n"] == fetches + 2
    assert obs.counter("mesh.retries").value == 2
    lanes = [attrs.get("lanes") for name, *_rest, attrs in obs.events()
             if name == "exec.dispatch"]
    assert lanes == [4, 2]


def test_reseed_mesh_lane_resolves_as_alone(db):
    """`lonely` is in no process: its first term is empty, a definitive
    empty answer; a gene whose join empties keeps the reference's
    reseed verdict, lane or not."""
    ex = fs.get_sharded_executor(db)
    genes = ["g1", "lonely", "g2", "g5"]
    plans = _plans(db, [grounded3(g) for g in genes])
    want = [ex.execute(p) for p in plans]
    got = ex.execute_many(plans)
    for g, w in zip(got, want):
        assert (g.count, g.reseed_needed) == (w.count, w.reseed_needed)
    # the served wrapper: a reseed lane is a decline (None), as alone
    served = compiler.execute_sharded_many_settle(
        db, plans, compiler.execute_sharded_many_dispatch(db, plans))
    assert [r is None for r in served] == [w.reseed_needed for w in want]


def test_commit_between_mesh_dispatch_and_settle_leaves_no_cache_insert():
    db = _db(result_cache_size=64)
    ex = fs.get_sharded_executor(db)
    plans = _plans(db, [shared2(f"g{i}") for i in range(3)])
    pending = ex.dispatch_many(plans)
    db.delta_version += 1      # a commit lands before settle
    got = ex.settle_many(pending)
    assert all(r is not None for r in got)
    assert len(ex.results._data) == 0
    # and with no commit in between the lanes are cached one by one
    got = ex.execute_many(plans)
    assert len(ex.results._data) == 3
    counters.reset_dispatch_counts()
    hits = ex.execute_many(plans)
    assert counters.DISPATCH_COUNTS["sharded"] == 0
    assert [h is g for h, g in zip(hits, got)] == [True] * 3


def test_mesh_lane_device_refs_are_made_on_demand(db):
    """A lane's `vals` / `valid` are sliced from the group's row-sharded
    output when first read, equal its host rows, and neither settle nor
    the served answer path slices anything."""
    ex = fs.get_sharded_executor(db)
    plans = _plans(db, [shared2(f"g{i}") for i in range(20, 23)])
    got = ex.execute_many(plans)
    for r in got:
        assert callable(r._vals) and callable(r._valid)   # not yet sliced
        assert r.host_vals.base is not None               # a view
    block = got[0].host_vals.base
    assert all(r.host_vals.base is block for r in got)    # of ONE block
    for r in got:
        assert r.vals.shape == r.host_vals.shape          # [S, cap, k]
        assert r.vals.shape[0] == S
        assert len(r.vals.sharding.device_set) == S       # still row-sharded
        assert np.array_equal(np.asarray(r.vals), r.host_vals)
        assert np.array_equal(np.asarray(r.valid), r.host_valid)
        assert not callable(r._vals)                      # kept once made
    # the served path reads the host copies and slices nothing
    from das_tpu.api.atomspace import DistributedAtomSpace

    das = DistributedAtomSpace(database_name="mesh-group", db=db)
    queries = [shared2(f"g{i}") for i in range(30, 33)]
    job = das.query_many_dispatch(queries)
    lanes = [j for _, j, _ in job.pending.programs[0][0]]
    assert len(lanes) == 3
    answers = job.settle()
    assert all(callable(j.result._vals) for j in lanes)
    assert answers == [das.query(q) for q in queries]


def test_group_program_moves_group_lanes_times_the_lone_programs_bytes(
        db, traced):
    ex = fs.get_sharded_executor(db)
    plans = _plans(db, [grounded3(f"g{i}") for i in range(40, 44)])
    ex.execute_many(plans)                  # the group program, traced
    ex.execute(plans[0])                    # and the lone one
    sig = ex._exec_job(plans[0], False).plan_sig()
    lone, _names = ex._cache[(sig, False)]
    (group, _n), = [v for k, v in ex._group_cache.items()
                    if k[0] == sig and not k[1]]
    assert lone.moved.bytes > 0
    assert group.moved.bytes == fused.GROUP_LANES * lone.moved.bytes
    # and the counter says so: a group's enqueue adds the padded lanes too
    before = obs.counter("mesh.collective_bytes").value
    ex.execute_many(plans)
    assert (obs.counter("mesh.collective_bytes").value - before
            == group.moved.bytes)
