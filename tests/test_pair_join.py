"""The join that verifies a pair before it counts a row (PR 44).

A join INTO a whole-type term that shares two or more variables with the
left side (`ops/join.py whole_type_join` -> `_pair_join_impl`) counts,
writes and reports to the retry ladder the rows that agree on EVERY
shared column, never the candidates of its first variable.  What that
buys: the baseline's own query, the whole-store 3-clause conjunction
`And(Interacts($1,$2), Member($1,$3), Member($2,$3))`, rides the fused
route at a cell's scale (benchmark cell `mem-analytic`).

  * the served path (gRPC, coalescer, planner, `das_fused`, answer path)
    against the benchmark's plain reference rule, seeded;
  * the same with `max_result_capacity` UNDER the candidate count and
    over the row count: the cell's situation at a CPU's size;
  * the join alone against a numpy set-join (duplicates, no match, all
    match, invalid rows, k = 2 and 3 shared columns, a lanes axis);
  * its output buffer is sized by its rows;
  * the scope and the counters it is traced by exist.
"""

import os
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import spec
from benchmark.reference import generator, plain
from das_tpu import obs
from das_tpu.api.atomspace import DistributedAtomSpace
from das_tpu.core.config import DasConfig
from das_tpu.ops import join as join_ops
from das_tpu.query import compiler, fused
from das_tpu.service.query_dsl import parse_query

SCALE = 0.004
SEEDS = (2**31 + 44, 440044)
RULE = spec.load_rule("three_var")
DSL = spec.Cell("mem-analytic").queries["three_var"]["dsl"]


def _das(store, tmp_path, **fields) -> DistributedAtomSpace:
    path = os.path.join(str(tmp_path), "kb.metta")
    generator.write_canonical(store, path)
    das = DistributedAtomSpace(
        database_name="pair", backend="tensor",
        config=DasConfig(result_cache_size=0, **fields))
    das.load_canonical_knowledge_base(path)
    os.remove(path)
    return das


def _candidates(store) -> int:
    """Pairs the posting index of ONE variable would expand in the
    conjunction's second join: every (pair, process) row of
    Interacts x Member times the other gene's memberships."""
    k = store.params["members_per_gene"]
    return 2 * len(store.interactions) * k * k


#: per case the configuration's fields: the default ceiling, and one
#: UNDER the second join's candidates and over its rows and over the
#: first join's (the cell's situation at a CPU's size; red on the
#: parent, whose index join sized that buffer by candidates and left
#: the query to the staged route)
CASES = {"default": {}, "ceiling_under_candidates":
         {"max_result_capacity": 1 << 18}}


@pytest.fixture(scope="module", params=[(s, c) for s in SEEDS for c in CASES],
                ids=lambda p: f"seed{p[0]}-{p[1]}")
def served(request, tmp_path_factory):
    from das_tpu.service.client import DasClient
    from das_tpu.service.server import serve

    seed, case = request.param
    store = generator.Store(SCALE, seed)
    kb = plain.PlainKB(store)
    das = _das(store, tmp_path_factory.mktemp("kb"), **CASES[case])
    server, service = serve(port=0, backend="tensor", block=False,
                            max_workers=8)
    token = service.attach_tenant("pair", das)
    client = DasClient(port=server.bound_port)
    yield client, token, kb, das, case
    client.close()
    server.stop(0).wait()


def test_the_served_conjunction_equals_the_plain_reference(served):
    client, token, kb, das, case = served
    want = kb.canonical_rows(RULE.rows(kb, None), columns=RULE.COLUMNS)
    assert 1000 < len(want) < 2500      # ~1,667 at any scale
    if case != "default":
        cap = das.db.config.max_result_capacity
        assert len(want) < cap < _candidates(kb.store)
    before = dict(compiler.ROUTE_COUNTS)
    reply = client.call("query", key=token, output_format="HANDLE", query=DSL)
    assert reply["success"], reply["msg"]
    assert plain.canonical_answer(reply["msg"]) == want
    after = compiler.ROUTE_COUNTS
    assert after["fused"] == before["fused"] + 1
    assert after["staged"] == before["staged"]
    assert after["host"] == before["host"]


def test_the_verified_joins_buffer_is_sized_by_its_rows(served):
    """The job's capacities after it settled: the verified join's at
    most max(the smallest class, 4 x its kept rows) and far under its
    candidates; the retry ladder never moved."""
    _client, _token, kb, das, _case = served
    ex = fused.get_executor(das.db)
    plans = compiler.plan_query(das.db, parse_query(DSL))
    job = ex._exec_job(list(plans), False)
    assert job is not None
    steps, _probes, _first = fused.whole_type_join_steps(
        job.sigs, job.index_joins)
    assert steps == (1,)
    out = job.dispatch()
    assert job.settle(jax.device_get(out), out) and job.rounds == 1
    kept = job.last_join_rows[1]
    assert kept == len(RULE.rows(kb, None))
    assert job.join_caps[1] <= max(64, 4 * kept) < _candidates(kb.store)
    assert job.result.host_vals.shape[0] == job.join_caps[1]
    # the rows offered to it are the first join's
    assert job.last_join_rows[0] == (2 * len(kb.store.interactions)
                                     * kb.store.params["members_per_gene"])


# -- the join alone --------------------------------------------------------


def _tables(rng, n_left, n_right, values, arity=3, pad=5):
    """Seeded tables; `values`: how many distinct ids a column draws
    from, one number for all columns or one per column."""
    values = (values,) * arity if isinstance(values, int) else values
    lv = rng.integers(0, values, (n_left, 3)).astype(np.int32)
    lm = rng.random(n_left) < 0.8
    targets = np.unique(
        rng.integers(0, values, (n_right, arity)).astype(np.int32), axis=0)
    tids = rng.integers(3, 5, targets.shape[0]).astype(np.int32)
    # the store's capacity padding: targets -2, type -1
    targets = np.concatenate([targets, np.full((pad, arity), -2, np.int32)])
    tids = np.concatenate([tids, np.full(pad, -1, np.int32)])
    return lv, lm, targets, tids


def _numpy_join(lv, lm, targets, tids, type_id, pairs, rvc, extra) -> list:
    out = []
    for i in np.flatnonzero(lm):
        for r in np.flatnonzero(tids == type_id):
            if all(lv[i, lc] == targets[r, rvc[rc]] for lc, rc in pairs):
                out.append(tuple(lv[i]) + tuple(targets[r, rvc[rc]]
                                                for rc in extra))
    return sorted(out)


def _run(lv, lm, targets, tids, type_id, pairs, rvc, extra, capacity):
    fn = jax.jit(join_ops._pair_join_impl, static_argnames=(
        "pairs", "right_var_cols", "right_extra", "capacity"))
    vals, valid, total = fn(
        jnp.asarray(lv), jnp.asarray(lm), jnp.asarray(targets),
        jnp.asarray(tids), np.int32(type_id), pairs=pairs,
        right_var_cols=rvc, right_extra=extra, capacity=capacity)
    vals, valid = np.asarray(vals), np.asarray(valid)
    return sorted(map(tuple, vals[valid])), int(total), vals, valid


JOIN_CASES = {
    # k = 2 of a 3-ary right side: one extra column, duplicates on
    # both sides of the key
    "k2_extra": dict(pairs=((0, 0), (1, 1)), extra=(2,), values=5),
    # k = 3: the right row adds nothing, a pair is a membership test
    "k3": dict(pairs=((0, 0), (1, 1), (2, 2)), extra=(), values=4),
    # the left's columns joined to OTHER positions of the right row
    "k2_crossed": dict(pairs=((0, 2), (2, 0)), extra=(1,), values=5),
    # values so sparse that nothing matches
    "no_match": dict(pairs=((0, 0), (1, 1)), extra=(2,), values=10**6),
    # one key everywhere: every left row pairs with every right row
    "all_match": dict(pairs=((0, 0), (1, 1)), extra=(2,), values=(1, 1, 9)),
}


@pytest.mark.parametrize("case", sorted(JOIN_CASES))
def test_the_join_alone_against_a_numpy_set_join(case):
    spec_ = JOIN_CASES[case]
    rng = np.random.default_rng(sorted(JOIN_CASES).index(case) + 44)
    lv, lm, targets, tids = _tables(rng, 60, 90, spec_["values"])
    rvc = (0, 1, 2)
    want = _numpy_join(lv, lm, targets, tids, 4, spec_["pairs"], rvc,
                       spec_["extra"])
    got, total, vals, valid = _run(lv, lm, targets, tids, 4, spec_["pairs"],
                                   rvc, spec_["extra"], capacity=8192)
    assert got == want and total == len(want)
    assert bool(want) == (case != "no_match")
    # rows that are not kept are zeroed, as every join's are
    assert not vals[~valid].any()


def test_an_overflowing_capacity_reports_the_exact_total():
    """`total` is the join's row count whatever the buffer holds: what
    the retry ladder sizes the next round by."""
    rng = np.random.default_rng(7)
    lv, lm, targets, tids = _tables(rng, 60, 90, (1, 1, 9))
    pairs, rvc, extra = ((0, 0), (1, 1)), (0, 1, 2), (2,)
    want = _numpy_join(lv, lm, targets, tids, 4, pairs, rvc, extra)
    assert len(want) > 64
    got, total, _vals, valid = _run(lv, lm, targets, tids, 4, pairs, rvc,
                                    extra, capacity=64)
    assert total == len(want) and valid.all()
    # the 64 slots hold 64 of the join's pairs (left rows repeat here)
    assert len(got) == 64 and not Counter(got) - Counter(want)


def test_a_ten_row_left_side_into_a_two_million_row_type():
    """The verified join sorts the WHOLE arity table with the left
    side, however small that is (what the planner prices it by:
    cost.join_step_cost holds both tables whole): ten left rows into
    2^21 rows of one type are answered exactly, from a buffer of the
    asked capacity.  What that costs the chip is a measurement
    (PERF.md §6 PR 44), not this test's."""
    rng = np.random.default_rng(2_097_152)
    n_right = 1 << 21
    targets = np.stack([rng.integers(0, 200_000, n_right),
                        rng.integers(0, 20_000, n_right)], 1).astype(np.int32)
    tids = np.where(np.arange(n_right) % 8 == 0, 3, 4).astype(np.int32)
    rows = rng.choice(np.flatnonzero(tids == 4), 10, replace=False)
    lv = np.concatenate(
        [targets[rows], np.arange(10, dtype=np.int32)[:, None]], axis=1)
    lv[5:, 1] += 20_000                 # five of the ten match nothing
    lm = np.ones(10, bool)
    pairs, rvc, extra = ((0, 0), (1, 1)), (0, 1), ()
    keys = {tuple(r) for r in lv[:, :2]}
    hit = np.flatnonzero((tids == 4) & np.isin(targets[:, 0], lv[:, 0]))
    want = sorted(
        tuple(l) for l in lv for r in hit
        if tuple(targets[r]) == tuple(l[:2]) and tuple(l[:2]) in keys)
    got, total, vals, _valid = _run(lv, lm, targets, tids, 4, pairs, rvc,
                                    extra, capacity=64)
    assert got == want and total == len(want) >= 5
    assert {g[2] for g in got} == set(range(5))
    assert vals.shape == (64, 3)


def test_the_join_batches_over_a_lanes_axis():
    """A group program vmaps the fold over its lanes: the left sides
    differ, the store's arrays are shared."""
    rng = np.random.default_rng(11)
    pairs, rvc, extra = ((0, 0), (1, 1)), (0, 1, 2), (2,)
    _lv, _lm, targets, tids = _tables(rng, 1, 90, 4)
    lefts = [_tables(rng, 40, 1, 4)[:2] for _ in range(3)]

    def lane(lv, lm):
        return join_ops.whole_type_join(
            lv, lm, (None, None, jnp.asarray(targets), jnp.asarray(tids)),
            np.int32(4), pairs, rvc, extra, 1024)

    vals, valid, total = jax.jit(jax.vmap(lane))(
        jnp.stack([l[0] for l in lefts]), jnp.stack([l[1] for l in lefts]))
    for k, (lv, lm) in enumerate(lefts):
        want = _numpy_join(lv, lm, targets, tids, 4, pairs, rvc, extra)
        got = sorted(map(tuple, np.asarray(vals[k])[np.asarray(valid[k])]))
        assert got == want and int(total[k]) == len(want)


def test_one_shared_variable_still_takes_the_posting_index(monkeypatch):
    """`whole_type_join` sends a join on ONE variable where it always
    went: the verified join is for two or more."""
    seen = []
    monkeypatch.setattr(join_ops, "_index_join_impl",
                        lambda *a: seen.append("index") or "i")
    monkeypatch.setattr(join_ops, "_pair_join_impl",
                        lambda *a: seen.append("pair") or "p")
    arrays = ("ks", "perm", "targets", "tids")
    assert join_ops.whole_type_join(0, 0, arrays, 4, ((0, 0),), (0, 1), (1,),
                                    64) == "i"
    assert join_ops.whole_type_join(0, 0, arrays, 4, ((0, 0), (1, 1)), (0, 1),
                                    (), 64) == "p"
    assert seen == ["index", "pair"]


# -- how it is traced ------------------------------------------------------


def test_the_scope_and_the_counters_are_declared():
    from das_tpu.obs import registry

    assert registry.PAIR_JOIN_SCOPE == "join.pair_verify"
    assert {"join.pair_left_rows", "join.pair_rows"} <= set(obs.COUNTER_NAMES)
    assert set(obs.metrics.COUNTERS) == set(obs.COUNTER_NAMES)
    bench = spec.load_benchmark()
    for name in ("ops.pair_join_ms_per_query", "ops.pair_join_roofline",
                 "ops.pair_join_left_rows_per_query"):
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == ["mem-analytic"]


def test_every_operation_of_the_join_sits_under_the_scope():
    rng = np.random.default_rng(3)
    lv, lm, targets, tids = _tables(rng, 20, 30, 3)
    lowered = jax.jit(join_ops._pair_join_impl, static_argnames=(
        "pairs", "right_var_cols", "right_extra", "capacity")).lower(
        jnp.asarray(lv), jnp.asarray(lm), jnp.asarray(targets),
        jnp.asarray(tids), np.int32(4), pairs=((0, 0), (1, 1)),
        right_var_cols=(0, 1, 2), right_extra=(2,), capacity=64)
    text = lowered.as_text(debug_info=True)
    assert "join.pair_verify" in text
    sorts = [ln for ln in text.splitlines() if "stablehlo.sort" in ln]
    assert len(sorts) == 1, "ONE sort of both sides together"


def test_a_settled_job_counts_rows_offered_and_rows_kept(served):
    """Tracing on: the job's `exec.verdict` span carries `pair_left_rows`
    and `pair_rows`, the counters move by the same, from the stats the
    round fetched anyway (no fetch beyond the round's one)."""
    _client, _token, kb, das, _case = served
    ex = fused.get_executor(das.db)
    plans = [list(compiler.plan_query(das.db, parse_query(DSL)))]
    obs.configure(enabled=True)
    try:
        obs.reset()
        fetches = fused.FETCH_COUNTS["n"]
        left0 = obs.counter("join.pair_left_rows").value
        kept0 = obs.counter("join.pair_rows").value
        pending = fused.dispatch_pending(
            ex.results, ex._exec_job, plans, False,
            build_jobs=ex._build_jobs)
        (result,) = fused.settle_pending(ex.results, pending)
        verdicts = [e for e in obs.events() if e[0] == "exec.verdict"]
    finally:
        obs.configure(enabled=False)
    kept = len(RULE.rows(kb, None))
    offered = (2 * len(kb.store.interactions)
               * kb.store.params["members_per_gene"])
    assert result.count == kept
    assert fused.FETCH_COUNTS["n"] == fetches + 1
    assert obs.counter("join.pair_left_rows").value - left0 == offered
    assert obs.counter("join.pair_rows").value - kept0 == kept
    assert len(verdicts) == 1
    attrs = verdicts[0][8]
    assert attrs["done"] and attrs["pair_left_rows"] == offered
    assert attrs["pair_rows"] == kept


# -- what the cell's deadline forced on the first join ---------------------


def test_a_long_vectors_int64_cumsum_is_two_32_bit_passes():
    """Past ASSOC_SCAN_MAX_ROWS the prefix sum of row counts is summed
    from carries (80 s less compile for the chip at a million rows):
    exact, also where the low words wrap."""
    rng = np.random.default_rng(0)
    n = join_ops.ASSOC_SCAN_MAX_ROWS + 3
    for high in (50, 2**32 - 1):
        x = rng.integers(0, high, n).astype(np.int64)
        got = np.asarray(jax.jit(join_ops._cumsum_i64)(jnp.asarray(x)))
        assert got.dtype == np.int64 and (got == np.cumsum(x)).all()
    text = jax.jit(join_ops._cumsum_i64).lower(
        jax.ShapeDtypeStruct((n,), jnp.int64)).as_text()
    assert "reduce_window" in text
    # a short vector keeps the log-depth scan (the cells' programs)
    short = jax.jit(join_ops._cumsum_i64).lower(
        jax.ShapeDtypeStruct((2048,), jnp.int64)).as_text()
    assert "reduce_window" not in short


def test_a_big_key_table_is_searched_by_scan():
    big = join_ops.SORT_SEARCH_MAX_KEYS + 1
    # a big left side: the posting-index join searches its type's slice
    # once, on 32-bit words (PR 45, tests/test_index_slice_search.py);
    # every other search of such a table stays a scan
    for n_left in (1 << 20, 1 << 24):
        assert join_ops.index_search_method(n_left, big) == "slice"
        assert join_ops._searchsorted_method(n_left, big) == "scan"
    # the rule below it is what it was
    assert join_ops._searchsorted_method(2048, 16) == "sort"
    assert join_ops._searchsorted_method(2048, 8_883_562) == "scan"
    assert join_ops._searchsorted_method(1 << 17, 1 << 20) == "sort"
