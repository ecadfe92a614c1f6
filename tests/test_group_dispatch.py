"""One program and one fetch per coalesced group (ISSUE 30).

`dispatch_many` + `settle_many_iter` partition the jobs of a batch by
`(plan_sig, count_only)` and enqueue ONE `das_fused_group` program per
same-signature group of two or more (query/fused.py _dispatch_round);
a job alone in its signature, and every job of a type without the group
hooks, runs the program it always ran (the mesh job has them since ISSUE
43: tests/test_mesh_group.py).  Pinned here: the answers are the
per-query `execute` answers row for row; programs enqueued == groups and
one FETCH_COUNTS tick a round; a lane over its capacity retries alone
while its group-mates stream in round one; the reseed verdict, the
commit guard, `cache_only`, the lone job's `das_fused` cache entry; the
on-demand device references of a lane.

Compile budget: two query shapes on a store of 83 genes: a lone and a
group program each, and their retry tiers."""

import numpy as np
import pytest

from das_tpu.ops import counters
from das_tpu.core.config import DasConfig
from das_tpu.query import compiler, fused
from das_tpu.query.ast import And, Link, Node, Variable
from das_tpu.storage.atom_table import load_metta_text
from das_tpu.storage.tensor_db import TensorDB

N_GENES, N_PROCS = 80, 8
HUB, HUB2 = "hub", "hub2"
#: members of a process: 20 genes and the two hubs
PER_PROC = 2 * N_GENES // N_PROCS + 2


def _store_text() -> str:
    """80 genes in two of eight processes each (20 a process), the two
    hubs in all eight; gene i interacts with gene i + 8, which shares its
    processes, and `lonely` is in no process at all."""
    lines = ["(: Gene Type)", "(: Process Type)", "(: Member Type)",
             "(: Interacts Type)"]
    lines += [f'(: "g{i}" Gene)' for i in range(N_GENES)]
    lines += [f'(: "{HUB}" Gene)', f'(: "{HUB2}" Gene)', '(: "lonely" Gene)']
    lines += [f'(: "p{j}" Process)' for j in range(N_PROCS)]
    for i in range(N_GENES):
        lines.append(f'(Member "g{i}" "p{i % N_PROCS}")')
        lines.append(f'(Member "g{i}" "p{(i + 1) % N_PROCS}")')
        lines.append(f'(Interacts "g{i}" "g{(i + 8) % N_GENES}")')
    lines += [f'(Member "{h}" "p{j}")' for j in range(N_PROCS)
              for h in (HUB, HUB2)]
    lines.append('(Interacts "lonely" "g0")')
    return "\n".join(lines)


def grounded3(gene):
    g = Node("Gene", gene)
    return And([
        Link("Member", [g, Variable("$3")], True),
        Link("Member", [Variable("$2"), Variable("$3")], True),
        Link("Interacts", [g, Variable("$2")], True),
    ])


def shared2(gene):
    return And([
        Link("Member", [Node("Gene", gene), Variable("$3")], True),
        Link("Member", [Variable("$2"), Variable("$3")], True),
    ])


def _db(**config):
    # the greedy seeds: every gene's first capacities are the same, so a
    # hub's overflow is met at settle and not planned around
    config.setdefault("use_planner", "off")
    return TensorDB(load_metta_text(_store_text()), DasConfig(**config))


@pytest.fixture(autouse=True)
def _cold_cap_store(monkeypatch):
    # CapStore off: capacities an earlier run learned and persisted
    # would seed the hub's group past its overflow
    monkeypatch.setenv("DAS_TPU_XLA_CACHE", "0")


@pytest.fixture(scope="module")
def db():
    return _db(result_cache_size=0)


def _plans(db, queries):
    return [compiler.plan_query(db, q) for q in queries]


def _rows(result):
    return sorted(map(tuple, np.asarray(result.host_vals)[
        np.asarray(result.host_valid)]))


def _mixed(n):
    """n queries of two shapes (every fifth a shared2), gene 3 asked for
    twice where there is room: an in-batch duplicate."""
    qs = [(shared2 if i % 5 == 4 else grounded3)(f"g{i}") for i in range(n)]
    if n >= 3:
        qs[-1] = grounded3("g0")
    return qs


@pytest.mark.parametrize("n", [1, 2, 3, 17, 64])
def test_group_answers_equal_per_query_execute(db, n):
    ex = fused.get_executor(db)
    plans = _plans(db, _mixed(n))
    want = [ex.execute(p) for p in plans]
    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    pending = ex.dispatch_many(plans)
    # programs enqueued == groups: one per shape (a shape asked for once
    # runs alone; one wider than the top rung is cut there), never one
    # per query
    keys = {fused.ResultCache.key(p, False): len(p) for p in plans}
    per_shape = [list(keys.values()).count(k) for k in set(keys.values())]
    programs = sum(-(-k // fused.GROUP_LANES) for k in per_shape)
    assert len(pending.programs) == programs <= 3
    assert counters.DISPATCH_COUNTS["fused"] == programs
    assert sum(len(m) for m, _ in pending.programs) == len(keys)
    got = dict(ex.settle_many_iter(pending))
    assert fused.FETCH_COUNTS["n"] == fetches + 1   # ONE transfer a round
    assert counters.DISPATCH_COUNTS["fused"] == programs   # and no retry
    assert sorted(got) == list(range(n))
    for i, ref in enumerate(want):
        assert got[i].count == ref.count
        assert got[i].var_names == ref.var_names
        assert got[i].reseed_needed == ref.reseed_needed
        assert _rows(got[i]) == _rows(ref)
    if n >= 3:
        assert got[n - 1] is got[0]   # the duplicate aliases ONE result


def test_count_only_group(db):
    ex = fused.get_executor(db)
    plans = _plans(db, [grounded3(f"g{i}") for i in range(5)])
    want = [ex.execute(p, count_only=True).count for p in plans]
    pending = ex.dispatch_many(plans, count_only=True)
    assert len(pending.programs) == 1
    got = ex.settle_many(pending)
    assert [r.count for r in got] == want
    assert all(r.vals is None and r.host_vals is None for r in got)


def test_lone_job_runs_the_das_fused_entry(db):
    """A group of one calls today's program: the same object from the
    same cache, and no group program is built for it."""
    ex = fused.get_executor(db)
    plans = _plans(db, [shared2("g7")])
    ex.execute(plans[0])
    entries = dict(ex._cache)
    groups = dict(ex._group_cache)
    job = ex._exec_job(plans[0], False)
    assert (job.plan_sig(), False) in entries
    got = ex.execute_many(plans)
    assert got[0].count == ex.execute(plans[0]).count
    assert ex._cache == entries and ex._group_cache == groups


def test_group_program_is_built_once_per_signature(db):
    ex = fused.get_executor(db)
    ex.execute_many(_plans(db, [shared2(f"g{i}") for i in range(3)]))
    built = len(ex._group_cache)
    # a group of another width pads to the same lanes: builds nothing
    ex.execute_many(_plans(db, [shared2(f"g{i}") for i in range(10, 27)]))
    assert len(ex._group_cache) == built
    assert {key[2] for key in ex._group_cache} == {fused.GROUP_LANES}
    # the hoisted slot: every lane's `Member $2 $3` has one key
    assert any(None in key[3] for key in ex._group_cache)


def test_identical_lanes_keep_their_lanes_axis(db):
    """Two cache keys, one ordered plan (the same terms written in
    another order): every input slot would hoist; the program still has
    a lane for each."""
    ex = fused.get_executor(db)
    a = compiler.plan_query(db, grounded3("g9"))
    b = compiler.plan_query(db, And(list(reversed(grounded3("g9").terms))))
    assert fused.ResultCache.key(a, False) != fused.ResultCache.key(b, False)
    pending = ex.dispatch_many([a, b])
    got = ex.settle_many(pending)
    assert got[0].count == got[1].count == ex.execute(a).count
    keys, key_axes, _f, fval_axes = fused.stack_lanes(
        [(np.int64(7), np.int32(1))] * 2, [(np.zeros(0, np.int32),) * 2] * 2, 4)
    assert key_axes == (0, None) and fval_axes == (None, None)
    assert keys[0].shape == (4,)


def test_wide_group_is_cut_at_group_lanes(db):
    ex = fused.get_executor(db)
    plans = _plans(db, [shared2(f"g{i}") for i in range(fused.GROUP_LANES + 2)])
    pending = ex.dispatch_many(plans)
    assert sorted(len(m) for m, _ in pending.programs) == [2, fused.GROUP_LANES]
    got = ex.settle_many(pending)
    assert [r.count for r in got] == [ex.execute(p).count for p in plans]


def test_overflowing_lane_retries_alone():
    """The hub's join is past the capacity its group was seeded with: its
    lane asks for more and re-dispatches ALONE in round two; its
    group-mates are yielded in round one."""
    db = _db(result_cache_size=0)
    ex = fused.get_executor(db)
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
    assert counters.DISPATCH_COUNTS["fused"] == 1
    (i, hub), = list(stream)
    assert i == 2
    assert fused.FETCH_COUNTS["n"] == fetches + 2
    assert counters.DISPATCH_COUNTS["fused"] == 2        # the hub, alone
    assert hub.count == N_PROCS * PER_PROC > 64
    assert [r.count for _, r in first] == [2 * PER_PROC] * 4
    # the capacities it learned seed the next group: no retry
    fetches = fused.FETCH_COUNTS["n"]
    again = ex.execute_many(plans)
    assert fused.FETCH_COUNTS["n"] == fetches + 1
    assert [r.count for r in again] == [r.count for r in
                                        (first[0][1], first[1][1], hub,
                                         first[2][1], first[3][1])]


def test_two_overflowing_lanes_retry_as_a_group():
    """Lanes that ask for the same new capacities share their retry:
    round two is ONE program of two lanes."""
    db = _db(result_cache_size=0)
    ex = fused.get_executor(db)
    plans = _plans(db, [shared2(g) for g in ("g1", HUB, "g2", HUB2)])
    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    got = ex.execute_many(plans)
    assert [r.count for r in got] == [2 * PER_PROC, N_PROCS * PER_PROC] * 2
    assert counters.DISPATCH_COUNTS["fused"] == 2
    assert fused.FETCH_COUNTS["n"] == fetches + 2


def test_reseed_lane_resolves_as_alone(db):
    """`lonely` is in no process: its first term is empty, a definitive
    empty answer; a gene whose join empties keeps the reference's
    reseed verdict, lane or not."""
    ex = fused.get_executor(db)
    genes = ["g1", "lonely", "g2", "g5"]
    plans = _plans(db, [grounded3(g) for g in genes])
    want = [ex.execute(p) for p in plans]
    got = ex.execute_many(plans)
    for g, w in zip(got, want):
        assert (g.count, g.reseed_needed) == (w.count, w.reseed_needed)
    tables = compiler.execute_fused_many(db, plans)
    for table, plan in zip(tables, plans):
        ref = compiler._execute_fused(db, plan)
        assert (table is None) == (ref is None)
        if table is not None:
            assert table.count == ref.count


def test_commit_between_dispatch_and_settle_leaves_no_cache_insert():
    db = _db(result_cache_size=64)
    ex = fused.get_executor(db)
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
    assert counters.DISPATCH_COUNTS["fused"] == 0
    assert [h is g for h, g in zip(hits, got)] == [True] * 3


def test_cache_only_enqueues_nothing():
    db = _db(result_cache_size=64)
    ex = fused.get_executor(db)
    plans = _plans(db, [shared2(f"g{i}") for i in range(4)])
    ex.execute_many(plans[:2])
    counters.reset_dispatch_counts()
    fetches = fused.FETCH_COUNTS["n"]
    pending = ex.dispatch_many(plans, cache_only=True)
    assert pending.programs == []
    got = ex.settle_many(pending)
    assert [r is not None for r in got] == [True, True, False, False]
    assert counters.DISPATCH_COUNTS["fused"] == 0
    assert fused.FETCH_COUNTS["n"] == fetches


class _Alone:
    """A job type without the group hooks (a tree job): `dispatch()` /
    `settle()` only."""

    count_only = False

    def __init__(self, log, tag):
        self.log, self.tag, self.result = log, tag, None

    def plan_sig(self):
        return "one signature for all"

    def dispatch(self):
        self.log.append(self.tag)
        return np.asarray([self.tag])

    def settle(self, host_out, dev_out):
        self.result = int(host_out[0])
        return True


def test_jobs_without_the_hooks_are_dispatched_alone(db):
    log = []
    cache = fused.ResultCache(_db(result_cache_size=0))
    pending = fused.dispatch_pending(
        cache, lambda plans, count_only: _Alone(log, plans[0].arity),
        _plans(db, [shared2("g1"), grounded3("g2"), shared2("g3")]), False,
    )
    assert [len(m) for m, _ in pending.programs] == [1, 1, 1]
    assert len(log) == 3
    assert fused.settle_pending(cache, pending) == [2, 2, 2]


def test_lane_device_refs_are_made_on_demand(db):
    """A lane's `vals` / `valid` are sliced from the group's output when
    first read, equal its host rows, and settle itself slices nothing."""
    ex = fused.get_executor(db)
    plans = _plans(db, [shared2(f"g{i}") for i in range(20, 23)])
    got = ex.execute_many(plans)
    for r in got:
        assert callable(r._vals) and callable(r._valid)   # not yet sliced
        assert r.host_vals.base is not None               # a view
    block = got[0].host_vals.base
    assert all(r.host_vals.base is block for r in got)    # of ONE block
    for r in got:
        assert np.array_equal(np.asarray(r.vals), r.host_vals)
        assert np.array_equal(np.asarray(r.valid), r.host_valid)
        assert not callable(r._vals)                      # kept once made
    # the served path's tables read the host copies and slice nothing
    plans = _plans(db, [shared2(f"g{i}") for i in range(30, 33)])
    pending = compiler.execute_fused_many_dispatch(db, plans)
    lanes = [job for _, job, _ in pending.programs[0][0]]
    tables = compiler.execute_fused_many_settle(db, plans, pending)
    assert all(t.host_vals is not None and t.vals is None for t in tables)
    assert all(callable(job.result._vals) for job in lanes)


# -- the lanes' lowering choice (ops/join.py lane_batched) ----------------


@pytest.mark.parametrize("n_queries,n_keys,alone,in_lanes", [
    (2048, 16, "sort", "compare_all"),     # grounded3's second join
    (2048, 256, "sort", "compare_all"),
    (2048, 512, "sort", "sort"),           # past LANE_COMPARE_KEYS
    (64, 8_883_562, "scan", "scan"),       # few queries, the whole table
    (1024, 16, "scan", "scan"),
])
def test_lane_batched_search_method(n_queries, n_keys, alone, in_lanes):
    """A 'sort' searchsorted against a tiny key table is 'compare_all'
    while a lane-batched body is traced (the batched sort compiles in
    23 s for the chip, the compare in 0.3), and what a lone program
    lowers to does not change."""
    from das_tpu.ops import join

    assert join._searchsorted_method(n_queries, n_keys) == alone
    with join.lane_batched():
        assert join._searchsorted_method(n_queries, n_keys) == in_lanes
        with join.lane_batched():
            pass
        assert join._searchsorted_method(n_queries, n_keys) == in_lanes
    assert join._searchsorted_method(n_queries, n_keys) == alone


def test_lane_batched_is_per_thread():
    """Another thread's trace (a lone program built while a group
    program is) sees no lanes."""
    import threading

    from das_tpu.ops import join

    seen = []
    with join.lane_batched():
        t = threading.Thread(
            target=lambda: seen.append(join._searchsorted_method(2048, 16)))
        t.start()
        t.join()
    assert seen == ["sort"]


def test_lanes_program_joins_equal_the_lanes_run_alone():
    """The join whose search changes lowering under lanes (2,048 left
    rows against a 16-row right table): every lane of lanes_program's
    output equals that lane's own join."""
    import jax

    from das_tpu.ops import join

    rng = np.random.default_rng(7)
    lanes, n_left, n_right, cap = 4, 2048, 16, 4096
    lv = rng.integers(0, 24, (lanes, n_left, 2)).astype(np.int32)
    lm = rng.random((lanes, n_left)) < 0.9
    rv = rng.integers(0, 24, (lanes, n_right, 2)).astype(np.int32)
    rm = rng.random((lanes, n_right)) < 0.9
    methods = []
    real = join._searchsorted_method

    def spy(n_queries, n_keys):
        methods.append(real(n_queries, n_keys))
        return methods[-1]

    def one(arrays, keys, fvals):
        (l_vals, l_valid), (r_vals, r_valid) = keys, fvals
        return join._join_tables_impl(
            l_vals, l_valid, r_vals, r_valid, ((1, 0),), (1,), cap)

    join._searchsorted_method = spy
    try:
        alone = [one(None, (lv[i], lm[i]), (rv[i], rm[i]))
                 for i in range(lanes)]
        assert set(methods) == {"sort"}
        del methods[:]
        grouped = jax.jit(fused.lanes_program(one, (0, 0), (0, 0)))(
            None, (lv, lm), (rv, rm))
        assert set(methods) == {"compare_all"}
        del methods[:]
        one(None, (lv[0], lm[0]), (rv[0], rm[0]))   # a lone trace, after
        assert set(methods) == {"sort"}
    finally:
        join._searchsorted_method = real
    for i in range(lanes):
        for got, want in zip(grouped, alone[i]):
            np.testing.assert_array_equal(np.asarray(got)[i], np.asarray(want))
    assert int(np.asarray(grouped[2]).max()) > 0
